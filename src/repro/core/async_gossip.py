"""Asynchronous / straggler-tolerant consensus — the paper's §V future work.

The paper measures (Table V) that one slow node stalls the whole synchronous
network every iteration and concludes that mitigating stragglers "requires
dealing with asynchronicity in the networks", left as future work. This
module implements it:

* ``AsyncConsensus`` — a gossip engine in which every round each node is
  awake independently with probability ``p_awake``; sleeping nodes neither
  send nor mix (their neighbors renormalize their weights over the awake
  subgraph, preserving double stochasticity per round, so the average is
  conserved and the iteration remains a valid consensus step).
* ``straggler_wall_clock`` — a wall-clock model comparing the synchronous
  network (every round costs the slowest node's delay) with the async one
  (a delayed node simply misses rounds; the round time stays nominal but
  more rounds are needed for the same contraction).

Execution modes (``fused`` flag, same architecture as the rest of core/):
  * fused (default) — the awake masks for all ``t_c`` rounds are pre-sampled
    with ``jax.random``, and the per-round doubly-stochastic matrices, the
    gossip recursion, the realized mixing-matrix product (for the exact
    debias), and the per-round send/awake counts are all built inside one
    jitted ``lax.scan``. One device dispatch per call instead of one host
    round-trip per gossip round.
  * host (``fused=False``) — the original pure-NumPy float64 loop, one
    ``_round_matrix`` sample + einsum per round. Kept as the correctness
    oracle (tests/test_fused_zoo.py runs both on identical injected masks).

The headline result (benchmarks/async_straggler.py): with one persistent
straggler of delay D >> t_round, synchronous S-DOT pays (t_round + D) per
round while async S-DOT pays t_round per round and only ~1/N of the mixing
opportunities are lost — wall-clock speedup approaching (t_round + D) /
t_round for large networks, at a modest increase in rounds-to-floor.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .consensus import realized_round_weights, safe_debias_scale
from .linalg import PRECISION
from .metrics import CommLedger
from .topology import Graph, local_degree_weights

__all__ = ["AsyncConsensus", "masked_async_rounds", "straggler_wall_clock"]


def masked_async_rounds(w, adj, awake, t_c, z_stack):
    """Traceable async gossip: ``t_c`` realized rounds + realized debias.

    w: (N, N) nominal weights; adj: (N, N) 0/1 adjacency; awake: (T, N) bool
    pre-sampled masks; t_c: number of live rounds (may be a *traced* budget
    read from a schedule array — rounds i >= t_c are masked out of the z / p
    recursion and contribute zero sends/counts, so the whole-run fused
    executors in sdot.py / fdot.py can call this inside their outer scan);
    z_stack: (N, ...). Returns (debiased z, (T,) directed sends per round,
    (T,) awake-node counts per round) — masked rounds report 0.0 for both.

    An all-asleep round renormalizes to the exact identity matrix (every
    weight returns to the diagonal) with zero sends, and the debias guard
    (``safe_debias_scale``) divides by 1.0 wherever the realized product
    carries no mass — an all-degenerate call returns its input bit-for-bit
    instead of scaling it by 1e6.
    """
    n = w.shape[0]
    off = ~jnp.eye(n, dtype=bool)
    wz = w.astype(z_stack.dtype)

    def round_(carry, inp):
        z, p = carry
        a, i = inp
        live = i < t_c
        both = jnp.outer(a, a)
        w_off, dd = realized_round_weights(wz, both, off)
        w_round = w_off + jnp.diag(dd)
        z_next = jnp.einsum("ij,j...->i...", w_round, z, precision=PRECISION)
        # only column 0 of the realized product is ever read (the debias
        # weight), so carry the (N,) vector p = Pi W e_1, not the (N, N)
        # product — O(N^2) per round instead of O(N^3)
        p_next = jnp.matmul(w_round, p, precision=PRECISION)
        sends = jnp.sum(jnp.where(off & both, adj, 0.0))
        count = jnp.sum(a.astype(jnp.float32))
        z = jnp.where(live, z_next, z)
        p = jnp.where(live, p_next, p)
        return (z, p), (jnp.where(live, sends, 0.0),
                        jnp.where(live, count, 0.0))

    e1 = jnp.zeros((n,), z_stack.dtype).at[0].set(1.0)
    (z, p), (sends, counts) = jax.lax.scan(
        round_, (z_stack, e1), (awake, jnp.arange(awake.shape[0])))
    scale = safe_debias_scale(p)                   # realized [Pi W e_1]_i
    bshape = (-1,) + (1,) * (z_stack.ndim - 1)
    return z / scale.reshape(bshape), sends, counts


@functools.partial(jax.jit, static_argnums=())
def _fused_async_run(w, adj, awake, z_stack):
    """All awake rounds of ``awake`` + realized-product debias, on device.

    Thin jitted wrapper over masked_async_rounds with every round live
    (t_c == T). Recompiles per distinct T (the scan length) —
    constant-budget callers compile once.
    """
    return masked_async_rounds(w, adj, awake, jnp.int32(awake.shape[0]),
                               z_stack)


@dataclasses.dataclass
class AsyncConsensus:
    """Gossip with per-round random node availability.

    Each round, node i is awake w.p. ``p_awake[i]``. The effective mixing
    matrix for the round keeps only edges between awake nodes and returns
    every skipped weight to the diagonal — doubly stochastic by
    construction, so sum_i Z_i is invariant and the debiasing of Alg. 1
    still applies (we track the realized product of mixing matrices for the
    exact per-node debias weight).
    """

    graph: Graph
    p_awake: np.ndarray          # (N,) probability each node is awake
    seed: int = 0
    fused: bool = True           # device-side scan vs host NumPy loop

    def __post_init__(self):
        self.weights = local_degree_weights(self.graph)
        self._rng = np.random.default_rng(self.seed)
        self._key = jax.random.PRNGKey(self.seed)
        if np.isscalar(self.p_awake) or np.ndim(self.p_awake) == 0:
            self.p_awake = np.full(self.graph.n_nodes, float(self.p_awake))
        self._w = jnp.asarray(self.weights, jnp.float32)
        self._adj = jnp.asarray(self.graph.adjacency, jnp.float32)

    def _round_matrix(self) -> Tuple[np.ndarray, np.ndarray]:
        """Sample one realized round: returns ``(w, awake)`` where ``w`` is
        the (N, N) doubly-stochastic mixing matrix over the awake subgraph
        and ``awake`` the (N,) bool availability mask drawn this round."""
        awake = self._rng.random(self.graph.n_nodes) < self.p_awake
        return self._apply_mask(awake), awake

    def _apply_mask(self, awake: np.ndarray) -> np.ndarray:
        """Realized mixing matrix for a given awake mask (host reference)."""
        w = self.weights.copy()
        n = self.graph.n_nodes
        mask = np.outer(awake, awake)
        off = ~np.eye(n, dtype=bool)
        dropped = np.where(off & ~mask, w, 0.0)
        w = np.where(off & mask, w, 0.0)
        dd = self.weights.diagonal() + dropped.sum(axis=1)
        # degenerate-row guard (mirrors realized_round_weights): a node with
        # no surviving link has an exactly-1 diagonal, not a 1 +- ulp sum
        isolated = ~(off & mask).any(axis=1)
        np.fill_diagonal(w, np.where(isolated, 1.0, dd))
        return w

    def sample_awake(self, t_c: int, t_max: Optional[int] = None) -> jnp.ndarray:
        """Pre-sample (t_c, N) awake masks from the engine's jax.random
        stream (each call advances the stream, mirroring the host rng).

        ``t_max`` pads the underlying draw to (t_max, N) and returns the
        first t_c rows. This matters for bit-level replay of the whole-run
        fused executors: they draw one (t_max, N) mask block per outer
        iteration inside the scan (static shape), so an eager oracle that
        wants the SAME realized rounds must draw with the same padded shape
        (a (t_c, N) threefry draw is NOT a prefix of the (t_max, N) one).
        """
        self._key, sub = jax.random.split(self._key)
        rows = int(t_c if t_max is None else t_max)
        masks = jax.random.bernoulli(
            sub, jnp.asarray(self.p_awake, jnp.float32),
            (rows, self.graph.n_nodes))
        return masks[:int(t_c)]

    def run_debiased(self, z_stack: jnp.ndarray, t_c: int,
                     ledger: Optional[CommLedger] = None,
                     awake: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """t_c async rounds + exact realized debias: approximates sum_j Z_j.

        ``awake`` optionally injects the (>= t_c, N) availability masks (used
        by the device-vs-host equivalence tests); only the first t_c rows are
        consumed, exactly like the host loop. By default the fused path draws
        them from jax.random and the host path from the NumPy rng.
        """
        if awake is not None and awake.shape[0] < int(t_c):
            raise ValueError(f"awake has {awake.shape[0]} rounds but "
                             f"t_c={t_c}")
        if self.fused:
            return self._run_fused(z_stack, int(t_c), ledger, awake)
        return self._run_host(z_stack, int(t_c), ledger, awake)

    def _run_fused(self, z_stack, t_c, ledger, awake):
        if awake is None:
            awake = self.sample_awake(t_c)
        else:
            awake = awake[:t_c]
        z = jnp.asarray(z_stack, jnp.float32)
        out, sends, counts = _fused_async_run(
            self._w, self._adj, jnp.asarray(awake, bool), z)
        if ledger is not None:
            sends = np.asarray(sends, np.float64)
            payload = float(np.prod(z_stack.shape[1:]))
            ledger.p2p += float(sends.sum())
            ledger.matrices += float(sends.sum())
            ledger.scalars += float(sends.sum()) * payload
            ledger.log_awake_rounds(np.asarray(counts))
        return out

    def _run_host(self, z_stack, t_c, ledger, awake):
        n = self.graph.n_nodes
        off = ~np.eye(n, dtype=bool)
        z = np.asarray(z_stack, np.float64)
        prod = np.eye(n)
        for t in range(t_c):
            if awake is None:
                w, a = self._round_matrix()
            else:
                a = np.asarray(awake[t], bool)
                w = self._apply_mask(a)
            z = np.einsum("ij,j...->i...", w, z)
            prod = w @ prod
            if ledger is not None:
                sends = float(((w > 0) & off).sum())   # off-diag messages
                ledger.p2p += sends
                ledger.matrices += sends
                ledger.scalars += sends * np.prod(z_stack.shape[1:])
                ledger.log_awake_rounds([int(a.sum())])
        p = prod[:, 0]                             # realized [Pi W e_1]_i
        scale = np.where(p > 1e-6, p, 1.0)         # same guard as the scan
        bshape = (-1,) + (1,) * (z_stack.ndim - 1)
        return jnp.asarray(z / scale.reshape(bshape), jnp.float32)


def straggler_wall_clock(*, n_nodes: int, t_round: float, delay: float,
                         rounds_sync: int, rounds_async: int) -> dict:
    """Wall-clock model, one persistent straggler (paper Table V setting).

    Synchronous: every round blocks on the straggler -> (t_round + delay).
    Asynchronous: rounds never block (the straggler is simply asleep while
    busy); it is awake a fraction t_round/(t_round+delay) of rounds.
    """
    sync = rounds_sync * (t_round + delay)
    async_ = rounds_async * t_round
    return {
        "sync_s": sync,
        "async_s": async_,
        "speedup": sync / async_ if async_ else float("inf"),
        "straggler_duty_cycle": t_round / (t_round + delay),
    }
