"""Self-healing gossip: a seeded, declarative NETWORK-fault layer.

``AsyncConsensus`` models i.i.d. node sleeping — the paper's straggler
study. Real overlays fail per-*link*, in bursts, and nodes crash and
rejoin; this module extends the realized-mixing machinery from node masks
to general EDGE masks so the whole algorithm zoo survives:

* **link drops** — each directed pair fails i.i.d. with ``p_drop`` per
  round (sampled symmetrically: a dropped link is dropped both ways, which
  is what keeps the realized round matrix doubly stochastic);
* **bursty outages** — a two-state Gilbert–Elliott Markov chain per edge
  (``p_bad`` to enter the bad state, ``p_good`` to recover, mean burst
  length 1/p_good); the per-edge state rides in the scan carry, across
  rounds AND outer iterations, so a chunked resume replays bursts exactly;
* **crash/rejoin** — a node leaves for a contiguous window of outer
  iterations (``crash_windows``): all its edges are masked, its iterate is
  frozen by the executors, and on rejoin it re-syncs from its neighbors
  through ordinary gossip;
* **payload corruption** — a node's outbound messages are scaled by
  ``corrupt_scale`` (or NaN-poisoned) with probability ``p_corrupt`` per
  round, and every receiver runs a detect-and-reject guard (NaN/norm
  screen, threshold ``guard_norm``): a poisoned round degrades to a
  dropped one — the sender's edges are masked both ways and its message is
  zeroed before mixing (so a NaN can never reach the einsum) — instead of
  diverging.

Every realized round renormalizes the surviving weights over the masked
edge set (``consensus.realized_round_weights`` — doubly stochastic for any
symmetric mask) and the realized mixing product ``p = Pi W e_1`` is
carried through the scan, so the exact debias of Alg. 1 applies under
arbitrary fault mixes and S-DOT/F-DOT/SA-DOT stay convergent
(``benchmarks/netfaults_bench.py`` measures the debiased-vs-uncorrected
gap). ``safe_debias_scale`` guards the all-links-down degenerate rounds.

Execution modes (same architecture as ``AsyncConsensus``):
  * fused — all per-round fault draws for an outer iteration are
    pre-sampled as ``(t_max, N, N)`` / ``(t_max, N)`` uniforms (the edge
    twin of ``sample_awake``'s node masks) and the realized rounds run in
    one ``lax.scan`` (``masked_faulty_rounds``), embeddable in the
    whole-run executors of sdot.py / fdot.py;
  * eager per-round (``run_rounds_eager``) — the same round function
    dispatched once per round from a Python loop; matches the fused scan
    bit-for-bit (pinned in tests/test_netfaults.py);
  * host (``fused=False``) — a pure-NumPy mirror of the round math, the
    human-auditable seeded oracle (identical masks, float32 arithmetic in
    the same operation order).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .consensus import (_record_engine_metrics, debias_table,
                        realized_round_weights, safe_debias_scale)
from .linalg import PRECISION
from .metrics import CommLedger
from .sparse import SparseW, auto_sparse
from .topology import Graph, local_degree_weights

__all__ = ["NetFaultModel", "FaultyConsensus", "masked_faulty_rounds",
           "sample_fault_blocks", "realized_debias"]

_CORRUPT_MODES = ("scale", "nan")
_DEBIAS_MODES = ("realized", "nominal")


# ---------------------------------------------------------------------------
# declarative fault model
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class NetFaultModel:
    """Declarative network-fault configuration (all faults compose).

    The scalar knobs pack into a small device vector (``params()``), so a
    sweep can stack one row per case and vmap the SAME compiled body over a
    fault grid — fault parameters are sweepable lane data, not recompile
    triggers. ``crash_windows`` is (node, start_iter, n_iters) triples at
    outer-iteration granularity; ``node_up(t_outer, n)`` lowers them to a
    (T, N) schedule operand.
    """

    p_drop: float = 0.0          # i.i.d. per-link drop prob per round
    p_bad: float = 0.0           # Gilbert–Elliott: good -> bad per round
    p_good: float = 1.0          # Gilbert–Elliott: bad -> good per round
    p_corrupt: float = 0.0       # per-node outbound corruption prob/round
    corrupt_mode: str = "scale"  # "scale" | "nan"
    corrupt_scale: float = 1e9   # payload blow-up factor in "scale" mode
    guard_norm: float = 1e6      # receiver reject threshold (max |entry|)
    crash_windows: Tuple[Tuple[int, int, int], ...] = ()

    def validate(self, n_nodes: Optional[int] = None,
                 t_outer: Optional[int] = None) -> "NetFaultModel":
        for name in ("p_drop", "p_bad", "p_good", "p_corrupt"):
            v = float(getattr(self, name))
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name}: must be in [0, 1], got {v}")
        if self.p_bad > 0.0 and self.p_good <= 0.0:
            raise ValueError("p_good: must be > 0 when p_bad > 0 "
                             "(a burst must be able to end)")
        if self.corrupt_mode not in _CORRUPT_MODES:
            raise ValueError(f"corrupt_mode: expected one of "
                             f"{_CORRUPT_MODES}, got {self.corrupt_mode!r}")
        if not float(self.corrupt_scale) > 0.0:
            raise ValueError(f"corrupt_scale: must be > 0, "
                             f"got {self.corrupt_scale}")
        if not float(self.guard_norm) > 0.0:
            raise ValueError(f"guard_norm: must be > 0, "
                             f"got {self.guard_norm}")
        for k, win in enumerate(self.crash_windows):
            if len(win) != 3:
                raise ValueError(f"crash_windows[{k}]: expected "
                                 "(node, start, len)")
            node, start, length = (int(x) for x in win)
            if node < 0 or (n_nodes is not None and node >= n_nodes):
                raise ValueError(f"crash_windows[{k}].node: {node} out of "
                                 f"range for {n_nodes} nodes")
            if start < 0:
                raise ValueError(f"crash_windows[{k}].start: must be >= 0, "
                                 f"got {start}")
            if length <= 0:
                raise ValueError(f"crash_windows[{k}].len: must be > 0, "
                                 f"got {length}")
            if t_outer is not None and start >= t_outer:
                raise ValueError(f"crash_windows[{k}].start: {start} is "
                                 f"past t_outer={t_outer}")
        return self

    def params(self) -> jnp.ndarray:
        """(6,) float32 device vector of the per-round scalar knobs.

        Layout: [p_drop, p_bad, p_good, p_corrupt, corrupt_value,
        guard_norm] — corrupt_value is NaN in "nan" mode so one compiled
        body serves both corruption modes.
        """
        cval = (np.nan if self.corrupt_mode == "nan"
                else float(self.corrupt_scale))
        return jnp.asarray([self.p_drop, self.p_bad, self.p_good,
                            self.p_corrupt, cval, self.guard_norm],
                           jnp.float32)

    def node_up(self, t_outer: int, n: int) -> np.ndarray:
        """(t_outer, N) float32 schedule: 0.0 while a node is crashed."""
        up = np.ones((max(int(t_outer), 1), int(n)), np.float32)
        for node, start, length in self.crash_windows:
            up[int(start):int(start) + int(length), int(node)] = 0.0
        return up[:int(t_outer)] if t_outer else up[:0]

    @property
    def mean_burst_len(self) -> float:
        return 1.0 / float(self.p_good) if self.p_good > 0 else float("inf")


# ---------------------------------------------------------------------------
# seeded pre-sampling (the edge-mask twin of AsyncConsensus.sample_awake)
# ---------------------------------------------------------------------------
def _sym_uniform(key, rows: int, n: int) -> jnp.ndarray:
    """(rows, N, N) uniforms, symmetrized by mirroring the upper triangle —
    one draw per undirected edge per round, so link faults hit both
    directions together (the symmetry that preserves double stochasticity).
    The diagonal is left at 0 (never read: masks only gate off-diagonal
    weights)."""
    u = jax.random.uniform(key, (rows, n, n))
    up = jnp.triu(u, 1)
    return up + jnp.swapaxes(up, 1, 2)


def sample_fault_blocks(key, n: int, rows: int):
    """Pre-sample one outer iteration's fault draws from a split key.

    Returns ``(u_drop, u_burst, u_corrupt)``: two (rows, N, N) symmetric
    uniform blocks (i.i.d. drops, Gilbert–Elliott transitions) and one
    (rows, N) uniform block (per-node payload corruption). The fused
    executors call this inside their outer scan with ``rows = t_max``
    (static shape); the eager oracle draws with the same padded shape and
    slices — a (t_c, ...) threefry draw is NOT a prefix of the
    (t_max, ...) one, exactly as with ``sample_awake``.
    """
    ku, kb, kc = jax.random.split(key, 3)
    return (_sym_uniform(ku, rows, n), _sym_uniform(kb, rows, n),
            jax.random.uniform(kc, (rows, n)))


# ---------------------------------------------------------------------------
# realized faulty rounds (traceable; the edge-mask twin of
# masked_async_rounds)
# ---------------------------------------------------------------------------
def _faulty_round(wz, adj_b, off, params, up_pair, node_up, z, p, ge,
                  u_drop, u_burst, u_cor):
    """One realized faulty round: mask -> renormalize -> mix -> account.

    Shared verbatim by the fused scan (``masked_faulty_rounds``) and the
    eager per-round oracle (``FaultyConsensus.run_rounds_eager``) so the
    two execution modes cannot drift — they apply the identical jaxpr per
    round and match bit for bit.
    """
    p_drop, p_bad, p_good, p_cor, cval, guard = (params[i]
                                                 for i in range(6))
    bshape = (-1,) + (1,) * (z.ndim - 1)
    axes = tuple(range(1, z.ndim))
    # Gilbert–Elliott per-edge chain: transition first, then the new state
    # gates this round (a burst that starts this round already bites)
    ge_next = jnp.where(ge, u_burst >= p_good, u_burst < p_bad)
    # payload corruption + receiver-side detect-and-reject screen
    factor = jnp.where(u_cor < p_cor, cval, jnp.float32(1.0))
    msg = z * factor.astype(z.dtype).reshape(bshape)
    finite = jnp.all(jnp.isfinite(msg), axis=axes)
    peak = jnp.max(jnp.abs(msg), axis=axes)          # NaN -> valid False
    valid = finite & (peak <= guard)
    # the surviving symmetric edge set: real edges between up nodes, not
    # dropped, not in a burst, and neither endpoint's payload rejected (a
    # poisoned sender degrades to a dropped node for this round)
    mask = (adj_b & up_pair & ~ge_next & (u_drop >= p_drop)
            & valid[:, None] & valid[None, :])
    w_off, dd = realized_round_weights(wz, mask, off)
    # zero rejected payloads BEFORE the einsum: a masked weight times a NaN
    # is still NaN — the screen must whiten the message, not just the edge
    msg_clean = jnp.where(valid.reshape(bshape), msg,
                          jnp.zeros((), z.dtype))
    # split form: the diagonal applies each node's OWN (uncorrupted) state,
    # off-diagonal weights apply the screened messages
    z_next = dd.reshape(bshape) * z + jnp.einsum("ij,j...->i...", w_off,
                                                 msg_clean,
                                                 precision=PRECISION)
    p_next = dd * p + jnp.matmul(w_off, p, precision=PRECISION)
    sends = jnp.sum(jnp.where(off & mask, 1.0, 0.0))
    count = jnp.sum(node_up)
    return z_next, p_next, ge_next, sends, count


def _sparse_faulty_round(sw, slot_ok, params, up, node_up_f, z, p, ge,
                         u_drop, u_burst, u_cor):
    """ELL-form twin of ``_faulty_round``: edge masks become (N, L) mask
    vectors over the stored slots.

    The round draws are the SAME dense symmetric uniforms the dense engine
    pre-samples — gathered at the ELL slots (``take_along_axis`` with the
    neighbor indices), so a sparse engine realizes bit-identical fault
    masks to its dense oracle and only the float reduction ORDER differs
    (gather-sum over L slots instead of an N-wide einsum row). Dropped
    mass returns to the diagonal per row (the sparse image of
    ``realized_round_weights``), with the same exactly-1.0 pin for a
    fully-isolated node. The Gilbert–Elliott state rides in ELL form
    (N, L): both directions of an edge gather the same symmetric uniform
    from an all-good start, so the slot states stay mirror-consistent with
    the dense (N, N) chain.
    """
    p_drop, p_bad, p_good, p_cor, cval, guard = (params[i]
                                                 for i in range(6))
    bshape = (-1,) + (1,) * (z.ndim - 1)
    axes = tuple(range(1, z.ndim))
    idx = sw.ell_idx
    ud = jnp.take_along_axis(u_drop, idx, axis=1)
    ub = jnp.take_along_axis(u_burst, idx, axis=1)
    ge_next = jnp.where(ge, ub >= p_good, ub < p_bad)
    factor = jnp.where(u_cor < p_cor, cval, jnp.float32(1.0))
    msg = z * factor.astype(z.dtype).reshape(bshape)
    finite = jnp.all(jnp.isfinite(msg), axis=axes)
    peak = jnp.max(jnp.abs(msg), axis=axes)          # NaN -> valid False
    valid = finite & (peak <= guard)
    # surviving slots: real (non-padded) edges between up nodes, not
    # dropped, not in a burst, neither endpoint's payload rejected
    mask = (slot_ok & up[:, None] & up[idx] & ~ge_next & (ud >= p_drop)
            & valid[:, None] & valid[idx])
    wv = sw.ell_val.astype(z.dtype)
    zero = jnp.zeros((), z.dtype)
    w_off = jnp.where(mask, wv, zero)
    dropped = jnp.where(slot_ok & ~mask, wv, zero).sum(axis=1)
    dd = sw.diag.astype(z.dtype) + dropped
    dd = jnp.where(mask.any(axis=1), dd, jnp.ones((), z.dtype))
    msg_clean = jnp.where(valid.reshape(bshape), msg, zero)
    # split form as in the dense round: diagonal applies the node's OWN
    # (uncorrupted, full-precision) state; masked off-diagonal slots apply
    # the screened neighbor messages through the SpMM hook
    z_next = (dd.reshape(bshape) * z
              + sw.offdiag_mix(jnp.zeros_like(sw.diag), w_off, msg_clean))
    p_next = dd * p + jnp.sum(w_off * jnp.take(p, idx), axis=1)
    sends = jnp.sum(jnp.where(mask, 1.0, 0.0))
    count = jnp.sum(node_up_f)
    return z_next, p_next, ge_next, sends, count


def _masked_sparse_faulty_rounds(sw, params, node_up, ge0, blocks, t_c,
                                 z_stack):
    """Sparse branch of ``masked_faulty_rounds`` (ge0: (N, L) ELL-form)."""
    n = sw.n
    slot_ok = (jnp.arange(sw.ell_width)[None, :]
               < sw.row_nnz[:, None])
    up = node_up > 0
    node_up_f = node_up.astype(jnp.float32)

    def round_(carry, inp):
        z, p, ge = carry
        u_drop, u_burst, u_cor, i = inp
        live = i < t_c
        z_next, p_next, ge_next, sends, count = _sparse_faulty_round(
            sw, slot_ok, params, up, node_up_f, z, p, ge,
            u_drop, u_burst, u_cor)
        z = jnp.where(live, z_next, z)
        p = jnp.where(live, p_next, p)
        ge = jnp.where(live, ge_next, ge)
        return (z, p, ge), (jnp.where(live, sends, 0.0),
                            jnp.where(live, count, 0.0))

    u_drop, u_burst, u_cor = blocks
    e1 = jnp.zeros((n,), z_stack.dtype).at[0].set(1.0)
    (z, p, ge), (sends, counts) = jax.lax.scan(
        round_, (z_stack, e1, ge0),
        (u_drop, u_burst, u_cor, jnp.arange(u_drop.shape[0])))
    return z, p, ge, sends, counts


def masked_faulty_rounds(w, adj, params, node_up, ge0, blocks, t_c,
                         z_stack):
    """Traceable faulty gossip: ``t_c`` realized edge-mask rounds.

    w: (N, N) nominal weights OR a ``SparseW`` (the sparse branch gathers
    the same dense fault draws at its ELL slots, so realized masks match
    the dense engine exactly; its ge0 is the engine's (N, L) ELL-form
    state); adj: (N, N) 0/1 adjacency (unused by the sparse branch — the
    structure lives in the SparseW); params: (6,)
    ``NetFaultModel.params()``; node_up: (N,) 0/1 crash mask for this outer
    iteration; ge0: (N, N) bool Gilbert–Elliott bad-state at entry (carried
    across calls); blocks: pre-sampled draws from ``sample_fault_blocks``
    (first axis >= t_c; rounds i >= t_c are masked out of every recursion
    exactly like ``masked_async_rounds``, so traced budgets work inside the
    whole-run executors). z_stack: (N, ...).

    Returns ``(z, p, ge, sends, counts)``: the UNdebiased mixed stack, the
    realized mixing product column ``p = Pi W e_1`` (divide via
    ``realized_debias`` for the exact correction, or by a nominal W^t e_1
    table row for the uncorrected arm benchmarks measure), the final burst
    state, and per-round send/up-node counts (masked rounds report 0.0).
    """
    if isinstance(w, SparseW):
        return _masked_sparse_faulty_rounds(w, params, node_up, ge0,
                                            blocks, t_c, z_stack)
    n = w.shape[0]
    off = ~jnp.eye(n, dtype=bool)
    wz = w.astype(z_stack.dtype)
    adj_b = adj > 0
    up = node_up > 0
    up_pair = up[:, None] & up[None, :]
    node_up_f = node_up.astype(jnp.float32)

    def round_(carry, inp):
        z, p, ge = carry
        u_drop, u_burst, u_cor, i = inp
        live = i < t_c
        z_next, p_next, ge_next, sends, count = _faulty_round(
            wz, adj_b, off, params, up_pair, node_up_f, z, p, ge,
            u_drop, u_burst, u_cor)
        z = jnp.where(live, z_next, z)
        p = jnp.where(live, p_next, p)
        ge = jnp.where(live, ge_next, ge)
        return (z, p, ge), (jnp.where(live, sends, 0.0),
                            jnp.where(live, count, 0.0))

    u_drop, u_burst, u_cor = blocks
    e1 = jnp.zeros((n,), z_stack.dtype).at[0].set(1.0)
    (z, p, ge), (sends, counts) = jax.lax.scan(
        round_, (z_stack, e1, ge0),
        (u_drop, u_burst, u_cor, jnp.arange(u_drop.shape[0])))
    return z, p, ge, sends, counts


def realized_debias(z, p):
    """Exact per-node debias by the realized mixing product (guarded)."""
    bshape = (-1,) + (1,) * (z.ndim - 1)
    return z / safe_debias_scale(p).astype(z.dtype).reshape(bshape)


@functools.partial(jax.jit, static_argnums=())
def _fused_faulty_run(w, adj, params, node_up, ge0, u_drop, u_burst, u_cor,
                      z_stack):
    """All rounds of the pre-sampled blocks, one dispatch (t_c == T)."""
    return masked_faulty_rounds(w, adj, params, node_up, ge0,
                                (u_drop, u_burst, u_cor),
                                jnp.int32(u_drop.shape[0]), z_stack)


@jax.jit
def _one_faulty_round(wz, adj_b, off, params, up_pair, node_up, z, p, ge,
                      u_drop, u_burst, u_cor):
    return _faulty_round(wz, adj_b, off, params, up_pair, node_up, z, p,
                         ge, u_drop, u_burst, u_cor)


@jax.jit
def _one_sparse_faulty_round(sw, slot_ok, params, up, node_up, z, p, ge,
                             u_drop, u_burst, u_cor):
    return _sparse_faulty_round(sw, slot_ok, params, up, node_up, z, p,
                                ge, u_drop, u_burst, u_cor)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class FaultyConsensus:
    """Gossip under the full network-fault taxonomy of ``NetFaultModel``.

    Wraps any explicit graph with seeded link drops, bursty outages,
    crash/rejoin and payload corruption, renormalizing every realized round
    (doubly stochastic by construction) and tracking the realized mixing
    product for the exact debias — the edge-mask generalization of
    ``AsyncConsensus``. The Gilbert–Elliott burst state and the RNG key
    persist on the engine between calls, mirroring how the fused whole-run
    executors carry both through their scan.

    ``debias``: "realized" divides by the carried ``Pi W e_1`` (the
    self-healing correction); "nominal" divides by the fault-free
    ``W^t e_1`` table row — the uncorrected arm whose error floor the
    benchmark shows plateauing ~10x higher.
    """

    graph: Graph
    faults: NetFaultModel = dataclasses.field(default_factory=NetFaultModel)
    seed: int = 0
    fused: bool = True           # device rounds vs host NumPy oracle
    debias: str = "realized"     # "realized" | "nominal"
    sparse: Optional[bool] = None         # None = auto_sparse policy
    payload_dtype: Optional[str] = None   # e.g. "bfloat16" (sparse only)

    def __post_init__(self):
        if self.debias not in _DEBIAS_MODES:
            raise ValueError(f"debias: expected one of {_DEBIAS_MODES}, "
                             f"got {self.debias!r}")
        self.faults.validate(self.graph.n_nodes)
        self.weights = local_degree_weights(self.graph)
        self._sparse = auto_sparse(self.graph.n_nodes, self.graph.density,
                                   self.sparse)
        if self._sparse and not self.fused:
            raise ValueError("sparse=True requires fused=True: the NumPy "
                             "host oracle is dense-only (use a dense "
                             "engine as the oracle instead)")
        if self.payload_dtype is not None and not self._sparse:
            raise ValueError("payload_dtype (bf16 gossip) requires the "
                             "sparse mixing path (sparse=True)")
        if self._sparse:
            self._w = SparseW.from_dense(self.weights,
                                         self.graph.adjacency,
                                         payload_dtype=self.payload_dtype)
            _record_engine_metrics(self._w)
        else:
            self._w = jnp.asarray(self.weights, jnp.float32)
        self._adj = jnp.asarray(self.graph.adjacency, jnp.float32)
        self._params = self.faults.params()
        self._debias_tables = {}
        self.reset()
        from ..obs import get_journal
        get_journal().event(
            "netfault_model", "chaos", n_nodes=self.graph.n_nodes,
            seed=int(self.seed), debias=self.debias,
            p_drop=float(self.faults.p_drop),
            p_bad=float(self.faults.p_bad),
            p_corrupt=float(self.faults.p_corrupt),
            n_crash_windows=len(self.faults.crash_windows))

    @property
    def n_nodes(self) -> int:
        return self.graph.n_nodes

    @property
    def is_sparse(self) -> bool:
        return self._sparse

    @property
    def payload_bytes_per_elem(self) -> float:
        """Wire bytes per payload element (2.0 under bf16 gossip)."""
        return 2.0 if self.payload_dtype == "bfloat16" else 4.0

    def reset(self) -> None:
        """Rewind the fault stream: fresh key, all links in the good state
        (ELL-form (N, L) burst state for sparse engines)."""
        self._key = jax.random.PRNGKey(self.seed)
        if isinstance(self._w, SparseW):
            self._ge = jnp.zeros((self.graph.n_nodes, self._w.ell_width),
                                 bool)
        else:
            self._ge = jnp.zeros((self.graph.n_nodes,) * 2, bool)

    def debias_row(self, t_c: int) -> jnp.ndarray:
        """Nominal (fault-free) debias row [W^{t_c} e_1] — the uncorrected
        arm's divisor (cached per t_c via the shared device table)."""
        t_c = int(t_c)
        if t_c not in self._debias_tables:
            self._debias_tables[t_c] = debias_table(self._w, t_c)[t_c]
        return self._debias_tables[t_c]

    def sample_faults(self, t_c: int, t_max: Optional[int] = None):
        """Pre-sample the next iteration's fault blocks, advancing the
        engine's jax.random stream exactly as the fused executors do (one
        split per outer iteration; ``t_max`` pads the draw shape for
        bit-level replay — see ``sample_fault_blocks``)."""
        self._key, sub = jax.random.split(self._key)
        rows = int(t_c if t_max is None else t_max)
        blocks = sample_fault_blocks(sub, self.graph.n_nodes, rows)
        return tuple(b[:int(t_c)] for b in blocks)

    def run_debiased(self, z_stack, t_c: int,
                     ledger: Optional[CommLedger] = None,
                     faults=None, node_up=None) -> jnp.ndarray:
        """``t_c`` realized faulty rounds + debias (realized or nominal).

        ``faults`` optionally injects pre-sampled blocks (the eager
        executors pass the padded draws so seeded eager runs replay the
        fused scan); ``node_up`` injects the (N,) crash mask for the
        current outer iteration (default: everyone up). The burst state
        advances on the engine across calls.
        """
        t_c = int(t_c)
        if faults is None:
            faults = self.sample_faults(t_c)
        else:
            faults = tuple(b[:t_c] for b in faults)
        if node_up is None:
            node_up = jnp.ones((self.graph.n_nodes,), jnp.float32)
        node_up = jnp.asarray(node_up, jnp.float32)
        z = jnp.asarray(z_stack, jnp.float32)
        if self.fused:
            zz, p, ge, sends, counts = _fused_faulty_run(
                self._w, self._adj, self._params, node_up, self._ge,
                *[jnp.asarray(b) for b in faults], z)
        else:
            zz, p, ge, sends, counts = self._run_host(z, node_up, faults)
        self._ge = ge
        if ledger is not None:
            sends_np = np.asarray(sends, np.float64)
            payload = float(np.prod(z_stack.shape[1:]))
            total = float(sends_np.sum())
            ledger.p2p += total
            ledger.matrices += total
            ledger.scalars += total * payload
            ledger.payload_bytes += (total * payload
                                     * self.payload_bytes_per_elem)
            ledger.log_awake_rounds(np.asarray(counts))
        if self.debias == "realized":
            return realized_debias(zz, p)
        bshape = (-1,) + (1,) * (z.ndim - 1)
        row = self.debias_row(t_c).astype(zz.dtype)
        return zz / row.reshape(bshape)

    def run_rounds_eager(self, z_stack, node_up, faults):
        """The per-round eager twin of the fused scan: one jitted dispatch
        of the SAME round function per round. Matches
        ``masked_faulty_rounds`` bit for bit (tests/test_netfaults.py) —
        the execution-mode oracle for the whole-run executors."""
        n = self.graph.n_nodes
        z = jnp.asarray(z_stack, jnp.float32)
        node_up = jnp.asarray(node_up, jnp.float32)
        up = node_up > 0
        p = jnp.zeros((n,), z.dtype).at[0].set(1.0)
        ge = self._ge
        u_drop, u_burst, u_cor = faults
        sends, counts = [], []
        if isinstance(self._w, SparseW):
            slot_ok = (jnp.arange(self._w.ell_width)[None, :]
                       < self._w.row_nnz[:, None])
            for t in range(u_drop.shape[0]):
                z, p, ge, s, c = _one_sparse_faulty_round(
                    self._w, slot_ok, self._params, up, node_up, z, p,
                    ge, u_drop[t], u_burst[t], u_cor[t])
                sends.append(s)
                counts.append(c)
            return z, p, ge, jnp.stack(sends), jnp.stack(counts)
        off = ~jnp.eye(n, dtype=bool)
        wz = self._w.astype(z.dtype)
        adj_b = self._adj > 0
        up_pair = up[:, None] & up[None, :]
        for t in range(u_drop.shape[0]):
            z, p, ge, s, c = _one_faulty_round(
                wz, adj_b, off, self._params, up_pair, node_up, z, p, ge,
                u_drop[t], u_burst[t], u_cor[t])
            sends.append(s)
            counts.append(c)
        return z, p, ge, jnp.stack(sends), jnp.stack(counts)

    def _run_host(self, z_stack, node_up, faults):
        """Pure-NumPy float32 oracle: identical masks and operation order
        as ``_faulty_round``, written independently for auditability."""
        n = self.graph.n_nodes
        off = ~np.eye(n, dtype=bool)
        w = np.asarray(self.weights, np.float32)
        adj_b = np.asarray(self.graph.adjacency) > 0
        p_drop, p_bad, p_good, p_cor, cval, guard = np.asarray(
            self._params, np.float32)
        node_up = np.asarray(node_up, np.float32)
        up = node_up > 0
        up_pair = np.outer(up, up)
        z = np.asarray(z_stack, np.float32)
        bshape = (-1,) + (1,) * (z.ndim - 1)
        axes = tuple(range(1, z.ndim))
        p = np.zeros((n,), np.float32)
        p[0] = 1.0
        ge = np.asarray(self._ge, bool)
        u_drop, u_burst, u_cor = (np.asarray(b) for b in faults)
        sends, counts = [], []
        for t in range(u_drop.shape[0]):
            ge = np.where(ge, u_burst[t] >= p_good, u_burst[t] < p_bad)
            factor = np.where(u_cor[t] < p_cor, cval,
                              np.float32(1.0)).astype(np.float32)
            msg = z * factor.reshape(bshape)
            with np.errstate(invalid="ignore"):
                finite = np.all(np.isfinite(msg), axis=axes)
                peak = np.max(np.abs(msg), axis=axes)
                valid = finite & (peak <= guard)
            mask = (adj_b & up_pair & ~ge & (u_drop[t] >= p_drop)
                    & valid[:, None] & valid[None, :])
            w_off = np.where(off & mask, w, np.float32(0.0))
            dd = (np.diag(w)
                  + np.where(off & ~mask, w, np.float32(0.0)).sum(axis=1))
            # degenerate-row guard (mirrors realized_round_weights): a
            # fully-isolated node's diagonal is exactly 1, not 1 +- ulp
            dd = np.where((off & mask).any(axis=1), dd, np.float32(1.0))
            msg_clean = np.where(valid.reshape(bshape), msg,
                                 np.float32(0.0))
            z = (dd.reshape(bshape) * z
                 + np.einsum("ij,j...->i...", w_off, msg_clean))
            p = dd * p + w_off @ p
            sends.append(float((off & mask).sum()))
            counts.append(float(node_up.sum()))
        return (jnp.asarray(z), jnp.asarray(p), jnp.asarray(ge),
                jnp.asarray(np.asarray(sends, np.float32)),
                jnp.asarray(np.asarray(counts, np.float32)))

    def realized_round_matrix(self, mask: np.ndarray) -> np.ndarray:
        """Host reference: the (N, N) realized doubly-stochastic round
        matrix for a given symmetric surviving-edge mask (used by tests to
        check stochasticity and mass conservation)."""
        n = self.graph.n_nodes
        off = ~np.eye(n, dtype=bool)
        mask = np.asarray(mask, bool)
        w = np.where(off & mask, self.weights, 0.0)
        dd = (self.weights.diagonal()
              + np.where(off & ~mask, self.weights, 0.0).sum(axis=1))
        np.fill_diagonal(w, np.where((off & mask).any(axis=1), dd, 1.0))
        return w
