"""Subspace error metrics and communication-cost accounting.

The error metric is the paper's eq. (11): the mean squared sine of the
principal angles between the estimated and true subspaces, equal (up to a
factor) to the chordal distance between the projectors.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .linalg import PRECISION

__all__ = [
    "subspace_error",
    "subspace_error_from_cross",
    "mean_subspace_error",
    "projector_distance",
    "principal_angles",
    "CommLedger",
    "p2p_per_consensus_round",
]


def subspace_error(q_true, q_hat) -> jnp.ndarray:
    """Paper eq. (11): E = (1/r) * sum_i (1 - sigma_i^2(Q^T Qhat)).

    Invariant to right-rotation of either argument. 0 iff span(Q)==span(Qhat).
    """
    return subspace_error_from_cross(
        jnp.matmul(q_true.T, q_hat, precision=PRECISION))


def subspace_error_from_cross(cross) -> jnp.ndarray:
    """Eq. (11) from a precomputed cross product ``Q_true^T Q_hat``.

    The fused F-DOT/d-PM executors assemble the cross product directly from
    zero-padded per-node slabs (the padded rows contribute nothing), so the
    metric never needs the concatenated global estimate.
    """
    s = jnp.linalg.svd(cross, compute_uv=False)
    r = cross.shape[0]
    return jnp.mean(1.0 - jnp.clip(s[:r], 0.0, 1.0) ** 2)


def mean_subspace_error(q_true, q_nodes, node_mask=None) -> jnp.ndarray:
    """Mean of eq. (11) over stacked per-node estimates q_nodes: (N, d, r).

    Traceable (SVD of N tiny r x r matrices) — the fused S-DOT executor
    evaluates this *inside* its outer scan so the whole error trace comes
    back as one device array instead of T_o per-iteration host syncs.

    ``node_mask`` (N,) restricts the mean to mask > 0 nodes — the ragged-N
    sweep engine pads small networks with isolated identity nodes whose
    estimates must not pollute the trace. With a mask of ones the weighted
    mean reduces to exactly the unmasked expression (same op order).
    """
    errs = jax.vmap(lambda q: subspace_error(q_true, q))(q_nodes)
    if node_mask is None:
        return errs.mean()
    m = node_mask.astype(errs.dtype)
    return jnp.sum(errs * m) / jnp.sum(m)


def projector_distance(q_true, q_hat) -> jnp.ndarray:
    """||QQ^T - Qhat Qhat^T||_2 — the quantity bounded by Theorem 1."""
    p1 = q_true @ q_true.T
    p2 = q_hat @ q_hat.T
    return jnp.linalg.norm(p1 - p2, ord=2)


def principal_angles(q_true, q_hat) -> jnp.ndarray:
    s = jnp.linalg.svd(q_true.T @ q_hat, compute_uv=False)
    return jnp.arccos(jnp.clip(s, -1.0, 1.0))


def p2p_per_consensus_round(adjacency: np.ndarray) -> float:
    """Average point-to-point sends per node per consensus round.

    One gossip round Z_i <- sum_j w_ij Z_j requires each node to send its
    block to every neighbor: sum of degrees / N messages per node. Matches
    the paper's MPI P2P counter (its tables report per-node averages).
    """
    n = adjacency.shape[0]
    return float(adjacency.sum() / n)


@dataclasses.dataclass
class CommLedger:
    """Accumulates communication events for an algorithm run.

    p2p        : point-to-point messages (paper's 'P2P' column), total over nodes
    matrices   : number of d-x-r matrix sends (the paper's 'unit' cost)
    scalars    : payload element count actually moved (for byte-level rooflines)
    awake_counts: per-round awake-node counts logged by async engines
                  (empty for synchronous runs — every node is awake)
    payload_bytes: wire bytes actually moved — ``scalars`` priced at the
                  engine's payload element width (4 for f32 gossip, 2 when
                  a sparse engine quantizes payloads to bf16), so the
                  accuracy-vs-bytes tradeoff curve reads straight off the
                  ledger
    """

    p2p: float = 0.0
    matrices: float = 0.0
    scalars: float = 0.0
    awake_counts: list = dataclasses.field(default_factory=list)
    payload_bytes: float = 0.0

    def log_awake_rounds(self, counts) -> None:
        """Record realized per-round awake-node counts (async gossip)."""
        self.awake_counts.extend(int(c) for c in np.asarray(counts).ravel())

    def mean_awake(self) -> float:
        """Mean awake nodes per round over the logged async rounds."""
        return float(np.mean(self.awake_counts)) if self.awake_counts else float("nan")

    def log_gossip_round(self, adjacency: np.ndarray, payload_elems: int,
                         bytes_per_elem: float = 4.0) -> None:
        sends = float(adjacency.sum())  # directed messages this round
        self.p2p += sends
        self.matrices += sends
        self.scalars += sends * payload_elems
        self.payload_bytes += sends * payload_elems * bytes_per_elem

    def log_gossip_rounds(self, schedule: np.ndarray, adjacency: np.ndarray,
                          payload_elems: int,
                          bytes_per_elem: float = 4.0) -> None:
        """Closed-form accounting for a whole run's consensus schedule.

        Equivalent to calling log_gossip_round once per round of every outer
        iteration (all increments are equal, so the sum is exact), but O(1)
        instead of O(sum schedule) Python-loop iterations — this is what the
        fused executor logs after its single device dispatch.
        """
        rounds = float(np.asarray(schedule, dtype=np.float64).sum())
        sends = float(adjacency.sum()) * rounds
        self.p2p += sends
        self.matrices += sends
        self.scalars += sends * payload_elems
        self.payload_bytes += sends * payload_elems * bytes_per_elem

    def per_node_p2p(self, n_nodes: int) -> float:
        return self.p2p / n_nodes

    def merged(self, other: "CommLedger") -> "CommLedger":
        return CommLedger(
            self.p2p + other.p2p,
            self.matrices + other.matrices,
            self.scalars + other.scalars,
            self.awake_counts + other.awake_counts,
            self.payload_bytes + other.payload_bytes,
        )

    def merge_from(self, other: "CommLedger") -> None:
        """In-place accumulate ``other`` (callers that own a running ledger
        fold a finished run's accounting into it, e.g. the fused baselines
        merging their Program's closed-form ledger)."""
        self.p2p += other.p2p
        self.matrices += other.matrices
        self.scalars += other.scalars
        self.awake_counts.extend(other.awake_counts)
        self.payload_bytes += other.payload_bytes


def _ledger_flatten(ledger: CommLedger):
    # awake_counts travels as one float64 leaf so the whole ledger round-trips
    # through array-only channels (checkpoint shards, worker result files)
    return ((ledger.p2p, ledger.matrices, ledger.scalars,
             np.asarray(ledger.awake_counts, np.float64),
             ledger.payload_bytes), None)


def _ledger_unflatten(_aux, children):
    p2p, matrices, scalars, awake, payload_bytes = children
    return CommLedger(float(p2p), float(matrices), float(scalars),
                      [int(c) for c in np.asarray(awake).ravel()],
                      float(payload_bytes))


# Registered pytree: a CommLedger checkpoints through checkpoint/manager.py
# (and ships across the multi-host launcher boundary) without ad-hoc field
# plucking — restore rebuilds the list-valued awake_counts, so
# ``log_awake_rounds`` keeps extending it exactly as before.
jax.tree_util.register_pytree_node(CommLedger, _ledger_flatten,
                                   _ledger_unflatten)
