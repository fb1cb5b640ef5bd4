"""Shared benchmark plumbing.

Every benchmark module exposes ``run() -> list[Row]``; ``benchmarks.run``
executes them all and prints one CSV. P2P accounting follows the paper's MPI
counter: one point-to-point message per directed edge per gossip round,
reported per node in thousands (K), matching Tables I-IX.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import jax.numpy as jnp

from repro.core.linalg import eigh_topr
from repro.core.topology import Graph
from repro.data.pipeline import gaussian_eigengap_data, partition_samples


@dataclasses.dataclass
class Row:
    name: str
    us_per_call: float           # wall time of the measured run, microseconds
    derived: Dict[str, object]   # table-specific fields

    def csv(self) -> str:
        kv = ";".join(f"{k}={v}" for k, v in self.derived.items())
        return f"{self.name},{self.us_per_call:.1f},{kv}"


def timed(fn: Callable, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


def interleaved_best_of(variants, repeats: int, *,
                        sync: Optional[Callable] = None):
    """Best-of-N walltimes with the variant order rotated every round.

    This container shows +-20% walltime jitter and throttles over time, so
    a single-pass A-then-B comparison is unreliable: whichever variant runs
    later eats the throttling. Rotating the order each round spreads the
    machine noise over every variant and the per-variant MINIMUM is the
    least-noise estimate of its true cost.

    ``variants``: list of (name, thunk) pairs; each thunk runs one
    measurement and returns its result. ``sync`` (optional) is called on
    the result before the clock stops (e.g. ``jax.block_until_ready`` on
    the result's arrays) — omit it if the thunks block internally.

    Returns ``(best, outs)``: name -> best seconds, name -> last result.
    """
    variants = list(variants)
    best = {name: float("inf") for name, _ in variants}
    outs = {}
    for i in range(max(1, repeats)):
        k = i % len(variants)
        for name, fn in variants[k:] + variants[:k]:
            t0 = time.perf_counter()
            out = fn()
            if sync is not None:
                sync(out)
            best[name] = min(best[name], time.perf_counter() - t0)
            outs[name] = out
    return best, outs


def sample_problem(*, d: int, r: int, n_nodes: int, n_per: int, gap: float,
                   seed: int = 0, repeated_top: bool = False):
    """Sample-partitioned PSA problem + ground truth of the global covariance."""
    x, _, _ = gaussian_eigengap_data(d, n_nodes * n_per, r, gap, seed=seed,
                                     repeated_top=repeated_top)
    blocks = partition_samples(x, n_nodes)
    covs = jnp.stack([b @ b.T / b.shape[1] for b in blocks])
    _, q_true = eigh_topr(covs.sum(0), r)
    return covs, q_true


def p2p_per_node_k(graph: Graph, rounds_total: int) -> float:
    """Average per-node P2P messages (K) after ``rounds_total`` gossip rounds."""
    return float(graph.adjacency.sum() / graph.n_nodes) * rounds_total / 1e3


# The paper's standard schedule set (Tables I-IV; cap = the experiment's
# max consensus iterations, implicitly 50 unless the table says otherwise).
PAPER_SCHEDULES = {
    "[0.5t+1]": ("lin_half", 50),
    "t+1": ("lin1", 50),
    "2t+1": ("lin2", 50),
    "50": ("const", None),
}
