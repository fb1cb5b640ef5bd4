"""Consensus-averaging engines.

Two interchangeable execution engines compute the same gossip recursion
``Z_i <- sum_{j in N_i} w_ij Z_j``:

* ``DenseConsensus``   — all node blocks stacked on one device; one gossip
  round is an einsum with the (N, N) weight matrix. This is the simulation
  engine used to reproduce the paper's tables (N = 10..200 nodes).

* ``SpmdConsensus``    — node blocks sharded over a mesh axis; gossip rounds
  are executed with jax.lax collectives inside ``shard_map``. A ring topology
  (circulant W) lowers to weighted ``ppermute`` rounds — the TPU-native
  analogue of the paper's MPI point-to-point exchange. Dense/irregular
  topologies fall back to one ``all_gather`` + local mix per round.

Both engines also expose the paper's debiasing step
``V_i = Z_i^{(Tc)} / [W^{Tc} e_1]_i`` (Alg. 1, step 11).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .linalg import PRECISION
from .sparse import SparseW, auto_sparse
from .topology import Graph, local_degree_weights, ring
from .metrics import CommLedger

__all__ = [
    "DenseConsensus",
    "FaultyConsensus",
    "SparseConsensus",
    "SpmdConsensus",
    "consensus_schedule",
    "debias_weights",
    "debias_table",
    "debiased_gossip",
    "debias_by_row",
    "gossip_mix",
    "masked_gossip",
    "realized_round_weights",
    "safe_debias_scale",
]


def __getattr__(name):
    # FaultyConsensus lives in netfaults.py (which imports this module);
    # re-export it lazily so `from repro.core.consensus import
    # FaultyConsensus` works without a circular import.
    if name == "FaultyConsensus":
        from .netfaults import FaultyConsensus
        return FaultyConsensus
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def realized_round_weights(wz, mask, off):
    """Renormalize the nominal weights over one realized round's surviving
    edges: the REALIZED-ROUND API shared by every fault model.

    ``wz``: (N, N) nominal doubly-stochastic weights; ``mask``: (N, N) bool,
    SYMMETRIC — edge (i, j) survived this round; ``off``: (N, N) bool
    off-diagonal selector. Returns ``(w_off, dd)`` where ``w_off`` keeps the
    surviving off-diagonal weights and ``dd`` is the per-node diagonal with
    every dropped weight returned to it. The realized round matrix
    ``w_off + diag(dd)`` is doubly stochastic for any symmetric mask (row
    sums are 1 by construction; column sums are 1 because mask symmetry
    makes the dropped mass per column equal the dropped mass per row), so
    the network average is conserved and the realized-product debias of
    Alg. 1 stays exact. ``masked_async_rounds`` uses this with the node
    outer-product mask; ``netfaults.masked_faulty_rounds`` with general
    edge masks (link drops, bursts, crashes, rejected payloads).

    Degenerate-row guard: a node whose every link dropped this round has a
    diagonal that is MATHEMATICALLY exactly 1 (the full nominal row sum),
    but float-summing the dropped weights yields 1 +- 1 ulp, so a long run
    of identity rounds would drift the iterate by ~1e-5. Pin fully-isolated
    rows to exactly 1.0: an all-asleep / all-links-down round becomes the
    exact identity matrix and a fully degenerate gossip call returns its
    input bit-for-bit."""
    w_off = jnp.where(off & mask, wz, 0.0)
    dropped = jnp.where(off & ~mask, wz, 0.0).sum(axis=1)
    dd = jnp.diag(wz) + dropped
    isolated = ~jnp.any(off & mask, axis=1)
    return w_off, jnp.where(isolated, jnp.ones((), wz.dtype), dd)


def safe_debias_scale(p):
    """Debias divisor from a realized mixing product ``p = [Pi W e_1]``.

    Degenerate-round guard: a round where every node sleeps (or every link
    is down) is an exact identity round, and an all-degenerate run leaves
    ``p`` at its e_1 initial value — entries that are EXACTLY zero. The old
    ``max(p, 1e-6)`` clamp divided by ~0 there, scaling the iterate by 1e6
    for no informational gain (the direction is all that survives the QR).
    Divide by 1.0 instead wherever the realized mass is below the clamp:
    same direction, bounded magnitude, and an all-degenerate gossip call
    returns its input bit-for-bit."""
    return jnp.where(p > 1e-6, p, jnp.ones((), p.dtype))


def gossip_mix(wz, z):
    """One gossip application ``out_i = sum_j w_ij z_j`` — THE dispatch
    seam between dense and sparse mixing. ``wz`` is either a dense (N, N)
    array (the einsum the paper-scale simulations always used — kept as
    the correctness oracle) or a ``core.sparse.SparseW`` (ELL SpMM via
    the Pallas kernel / gather fallback). Every consensus path — fused
    executors included — mixes through this function, so an engine
    switching to sparse storage changes ONLY the storage/kernel, not the
    algebra around it.
    """
    if isinstance(wz, SparseW):
        return wz.mix(z)
    return jnp.einsum("ij,j...->i...", wz, z, precision=PRECISION)


@functools.partial(jax.jit, static_argnums=(2,))
def _dense_gossip(w, z_stack: jnp.ndarray, t_c: int) -> jnp.ndarray:
    wz = w.astype(z_stack.dtype)

    def round_(z, _):
        return gossip_mix(wz, z), None

    out, _ = jax.lax.scan(round_, z_stack, None, length=t_c)
    return out


def masked_gossip(w, z_stack: jnp.ndarray, t_c: jnp.ndarray,
                  t_max: int) -> jnp.ndarray:
    """``t_c`` gossip rounds where ``t_c`` is a *traced* value (<= t_max).

    The scan always runs ``t_max`` rounds and masks rounds past t_c, so a
    varying per-outer-iteration consensus budget stays inside one compiled
    program (this is the inner scan of the fused S-DOT executor). Round
    i < t_c applies exactly the same mix as _dense_gossip, in the same
    order — results match the eager engine to float-op identity.
    ``w`` may be dense or a ``SparseW`` (see ``gossip_mix``).
    """
    wz = w.astype(z_stack.dtype)

    def round_(z, i):
        z_next = gossip_mix(wz, z)
        return jnp.where(i < t_c, z_next, z), None

    out, _ = jax.lax.scan(round_, z_stack, jnp.arange(t_max))
    return out


@functools.partial(jax.jit, static_argnums=(1,))
def debias_table(w, t_max: int) -> jnp.ndarray:
    """Device-side debias weights [W^t e_1] for every t in 0..t_max at once.

    Returns (t_max + 1, N): row t equals ``debias_weights(w, t)`` (same
    1e-6 clamp), computed as one cumulative scan of W^T matvecs instead of a
    host-side ``np.linalg.matrix_power`` per outer iteration. Row t is
    indexed *inside* the fused executor's outer scan by the traced budget.
    ``w`` may be a ``SparseW`` (symmetric by construction, so the W^T
    matvec is the ordinary sparse mix — O(nnz) per row of the table).
    """
    n = w.shape[0]
    dtype = jnp.float32 if isinstance(w, SparseW) else w.dtype
    e1 = jnp.zeros((n,), dtype).at[0].set(1.0)

    def step(p, _):
        # SparseW is symmetric by contract, so W^T p is the ordinary mix;
        # the dense branch keeps the exact original matvec op
        p_next = (w.mix(p) if isinstance(w, SparseW)
                  else jnp.matmul(w.T, p, precision=PRECISION))
        return p_next, p_next

    _, rows = jax.lax.scan(step, e1, None, length=t_max)
    table = jnp.concatenate([e1[None], rows], axis=0)
    return jnp.maximum(table, 1e-6)


def debiased_gossip(w: jnp.ndarray, table: jnp.ndarray, z_stack: jnp.ndarray,
                    t_c: jnp.ndarray, t_max: int) -> jnp.ndarray:
    """masked_gossip + debias-by-table-row: the fused executor's inner step.

    Fully traceable (t_c may be a traced budget from the schedule array);
    numerically this is run_debiased with the host matrix_power replaced by
    table[t_c]. Free function so one jit cache serves every engine with the
    same shapes.
    """
    return debias_by_row(table, masked_gossip(w, z_stack, t_c, t_max), t_c)


def debias_by_row(table: jnp.ndarray, z_stack: jnp.ndarray,
                  t_c: jnp.ndarray) -> jnp.ndarray:
    """Divide each node's block by its weight in the debias table's row
    ``t_c`` (traceable; ``debiased_gossip``'s second half)."""
    scale = table[t_c]                                       # (N,)
    bshape = (-1,) + (1,) * (z_stack.ndim - 1)
    return z_stack / scale.astype(z_stack.dtype).reshape(bshape)


def debias_weights(w: np.ndarray, t_c: int) -> np.ndarray:
    """[W^{Tc} e_1]_i for every node i (the imperfect-averaging correction).

    Clamped away from zero: when t_c is smaller than a node's distance from
    node 0, the paper's debias weight is exactly 0 and V_i would be undefined
    (0/0). Early SA-DOT iterations hit this on sparse graphs; the clamp keeps
    the iterate finite — the local QR renormalizes, so only the *direction*
    matters and convergence is unaffected (the early iterate is inaccurate by
    design, cf. the SA-DOT schedule rationale).
    """
    n = w.shape[0]
    e1 = np.zeros(n)
    e1[0] = 1.0
    out = np.linalg.matrix_power(w.T, t_c) @ e1
    return np.maximum(out, 1e-6)


def consensus_schedule(kind: str, t_outer: int, t_max: int = 50, cap: Optional[int] = None):
    """Per-outer-iteration consensus budgets T_{c,t} used in the paper's tables.

    kind: 'const'   -> [t_max] * t_outer                      (S-DOT)
          'lin_half'-> ceil(0.5 t + 1)                         (SA-DOT, Table I)
          'lin1'    -> t + 1
          'lin2'    -> 2 t + 1
          'lin5'    -> 5 t + 1
    ``cap`` clips every entry (the paper's min(., 200) variants).
    """
    t = np.arange(1, t_outer + 1, dtype=np.float64)
    if kind == "const":
        sched = np.full(t_outer, float(t_max))
    elif kind == "lin_half":
        sched = np.ceil(0.5 * t + 1)
    elif kind == "lin1":
        sched = t + 1
    elif kind == "lin2":
        sched = 2 * t + 1
    elif kind == "lin5":
        sched = 5 * t + 1
    else:
        raise ValueError(f"unknown schedule kind: {kind}")
    if cap is not None:
        sched = np.minimum(sched, cap)
    return sched.astype(np.int64)


def _record_engine_metrics(sw: SparseW) -> None:
    """Publish a sparse engine's structure to the obs metrics registry
    (visible in ``python -m repro.obs summary``/``prom``): nnz/density
    gauges. The kernel path depends on the payload width, which only a
    solve knows, so ``sdot`` counts it per solve (``ell_pallas_rounds``)."""
    from ..obs import metrics
    reg = metrics()
    reg.gauge("gossip_sparse_nnz").set(sw.nnz)
    reg.gauge("gossip_sparse_density").set(sw.density)
    reg.gauge("gossip_sparse_ell_width").set(sw.ell_width)
    if sw.payload_dtype is not None:
        reg.counter("gossip_bf16_engines_total").inc()


@dataclasses.dataclass
class DenseConsensus:
    """Single-device gossip simulator over an explicit graph.

    ``sparse`` selects the mixing storage/kernel: ``True`` stores W as a
    ``SparseW`` (padded-ELL SpMM rounds — O(nnz k) instead of O(N^2 k)),
    ``False`` forces the dense einsum, ``None`` (default) auto-enables
    sparse mixing only for networks that are both large and sparse
    (``sparse.auto_sparse`` — never at the paper's table scales, so
    existing seeded results are untouched). Either storage flows through
    the same ``gossip_mix`` seam in every fused executor, since they all
    embed ``self._w`` as a Program operand.
    """

    graph: Graph
    weights: Optional[np.ndarray] = None
    sparse: Optional[bool] = None
    payload_dtype: Optional[str] = None   # e.g. "bfloat16" (sparse only)

    def __post_init__(self):
        if self.weights is None:
            self.weights = local_degree_weights(self.graph)
        self._sparse = auto_sparse(self.graph.n_nodes, self.graph.density,
                                   self.sparse)
        if self._sparse:
            self._w = SparseW.from_dense(self.weights, self.graph.adjacency,
                                         payload_dtype=self.payload_dtype)
            _record_engine_metrics(self._w)
        elif self.payload_dtype is not None:
            raise ValueError("payload_dtype (bf16 gossip) requires the "
                             "sparse mixing path")
        else:
            self._w = jnp.asarray(self.weights)
        self._debias_tables = {}  # t_max -> (t_max+1, N) device table

    @property
    def is_sparse(self) -> bool:
        return self._sparse

    def gossip_path(self, width: int) -> Optional[str]:
        """The path of one sparse gossip round over a payload of ``width``
        columns ('pallas' or a fallback: ``SparseW.kernel_path``), or None
        on dense mixing."""
        return self._w.kernel_path(width) if self._sparse else None

    @property
    def payload_bytes_per_elem(self) -> float:
        """Wire bytes per payload element (ledger pricing): 2 when the
        sparse engine quantizes gossip payloads to bf16, else 4 (f32)."""
        return 2.0 if self.payload_dtype == "bfloat16" else 4.0

    def run(self, z_stack: jnp.ndarray, t_c: int) -> jnp.ndarray:
        """t_c gossip rounds on stacked blocks z_stack: (N, ...)."""
        return _dense_gossip(self._w, z_stack, int(t_c))

    def run_debiased(self, z_stack: jnp.ndarray, t_c: int,
                     ledger: Optional[CommLedger] = None) -> jnp.ndarray:
        """Gossip + per-node debias: approximates sum_j Z_j at every node."""
        out = self.run(z_stack, int(t_c))
        if self._sparse:
            # device-table row instead of the host O(N^3) matrix_power —
            # the whole point of the sparse engine is N where that
            # host power is unaffordable
            scale = self.debias_table(int(t_c))[int(t_c)]
        else:
            scale = jnp.asarray(debias_weights(self.weights, int(t_c)),
                                out.dtype)
        if ledger is not None:
            payload = int(np.prod(z_stack.shape[1:]))
            # closed form (identical increments per round), not an O(t_c)
            # host loop — eager B-DOT at t_c=50 was burning host time on
            # pure accounting
            ledger.log_gossip_rounds([int(t_c)], self.graph.adjacency,
                                     payload, self.payload_bytes_per_elem)
        bshape = (-1,) + (1,) * (z_stack.ndim - 1)
        return out / scale.astype(out.dtype).reshape(bshape)

    def debias_table(self, t_max: int) -> jnp.ndarray:
        """Cached (t_max + 1, N) table of [W^t e_1] rows (see debias_table)."""
        t_max = int(t_max)
        if t_max not in self._debias_tables:
            self._debias_tables[t_max] = debias_table(self._w, t_max)
        return self._debias_tables[t_max]

    def run_debiased_scan(self, z_stack: jnp.ndarray, t_c: jnp.ndarray, *,
                          t_max: int,
                          table: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Traceable twin of run_debiased, usable inside jit / lax.scan.

        ``t_c`` may be a traced int32 (the per-outer-iteration budget pulled
        from the schedule array); ``t_max`` is the static scan length (the
        schedule's max). PRECONDITION: t_c <= t_max — the masked scan caps
        gossip at t_max rounds and the table row gather clamps, so a larger
        t_c would silently return the t_max answer (checked here for
        concrete t_c; traced callers are responsible, as the fused executor
        is by construction). Gossip is a masked scan and the debias divides
        by a row of the precomputed device table — no host work, no
        recompile per distinct t_c. Accounting is NOT done here: the fused
        executor logs the whole schedule in closed form
        (CommLedger.log_gossip_rounds).
        """
        if isinstance(t_c, (int, np.integer)) and t_c > t_max:
            raise ValueError(f"t_c={t_c} exceeds the scan length t_max={t_max}")
        if table is None:
            table = self.debias_table(t_max)
        return debiased_gossip(self._w, table, z_stack, t_c, t_max)


@dataclasses.dataclass
class SparseConsensus(DenseConsensus):
    """Forced-sparse gossip engine: CSR/ELL mixing regardless of size.

    A ``DenseConsensus`` whose weight storage is always ``SparseW`` —
    every gossip round is an ELL SpMM (Pallas kernel on TPU, gather/
    einsum fallback elsewhere) and the debias table builds by sparse
    matvec. Plugs into every fused executor through the same ``_w`` /
    ``debias_table`` operand seam, so S-DOT/SA-DOT/F-DOT/B-DOT and the
    baselines run sparse without touching their Program definitions.

    ``payload_dtype="bfloat16"`` additionally quantizes the gossip
    payload (the neighbor messages, not each node's own state) to bf16
    with f32 accumulation; the comm ledger then prices bytes at 2/elem
    (``tests/test_sparse.py`` pins the quantized round and the halved
    ledger bytes).
    """

    def __post_init__(self):
        if self.sparse is False:
            raise ValueError("SparseConsensus is the forced-sparse engine;"
                             " use DenseConsensus for dense mixing")
        self.sparse = True
        super().__post_init__()


class SpmdConsensus:
    """Gossip over a mesh axis using lax collectives inside shard_map.

    Node i's block lives on mesh position i along ``axis``. For a ring
    topology, W is circulant: one round is
        z <- w_self * z + w_left * ppermute(z, +1) + w_right * ppermute(z, -1)
    For general graphs one round is an all_gather + local weighted mix —
    correct everywhere, cheaper only when the payload is small (which it is:
    the paper's payloads are d x r with r << d, and F-DOT's are r x r).
    """

    def __init__(self, mesh: Mesh, axis: str, graph: Optional[Graph] = None,
                 weights: Optional[np.ndarray] = None):
        self.mesh = mesh
        self.axis = axis
        self.n = mesh.shape[axis]
        self.graph = graph if graph is not None else ring(self.n)
        self.weights = weights if weights is not None else local_degree_weights(self.graph)
        if self.weights.shape != (self.n, self.n):
            raise ValueError("weight matrix does not match mesh axis size")
        self._is_ring = self._detect_ring()
        self._w = jnp.asarray(self.weights)
        self._debias_tables = {}  # t_max -> (t_max+1, N) device table
        self._spmd_programs = {}  # (t_max, trace_err) -> sdot_spmd program

    def _detect_ring(self) -> bool:
        return np.array_equal(self.graph.adjacency, ring(self.n).adjacency)

    def _ring_coeffs(self):
        w = self.weights
        n = self.n
        w_self = float(w[0, 0])
        w_next = float(w[0, (0 + 1) % n])
        w_prev = float(w[0, (0 - 1) % n])
        return w_self, w_prev, w_next

    def gossip_rounds(self, z: jnp.ndarray, t_c: int) -> jnp.ndarray:
        """t_c gossip rounds; z is the *local* block inside shard_map."""
        axis = self.axis
        if self._is_ring and self.n > 2:
            w_self, w_prev, w_next = self._ring_coeffs()
            fwd = [(i, (i + 1) % self.n) for i in range(self.n)]
            bwd = [(i, (i - 1) % self.n) for i in range(self.n)]

            def round_(zz, _):
                zp = jax.lax.ppermute(zz, axis, fwd)   # receives from i-1
                zn = jax.lax.ppermute(zz, axis, bwd)   # receives from i+1
                return w_self * zz + w_prev * zp + w_next * zn, None

            out, _ = jax.lax.scan(round_, z, None, length=t_c)
            return out
        # general topology: gather all blocks, mix with my row of W^{t_c}? No —
        # one round at a time keeps semantics identical to DenseConsensus.
        wj = jnp.asarray(self.weights, z.dtype)
        idx = jax.lax.axis_index(axis)

        def round_(zz, _):
            allz = jax.lax.all_gather(zz, axis)            # (N, ...)
            row = jax.lax.dynamic_slice_in_dim(wj, idx, 1, 0)[0]  # (N,)
            mixed = jnp.tensordot(row, allz, axes=(0, 0),
                                  precision=PRECISION)
            return mixed, None

        out, _ = jax.lax.scan(round_, z, None, length=t_c)
        return out

    def gossip_rounds_masked(self, z: jnp.ndarray, t_c: jnp.ndarray,
                             t_max: int) -> jnp.ndarray:
        """``t_c`` gossip rounds inside shard_map where ``t_c`` is *traced*.

        The SPMD twin of ``masked_gossip``: the scan always runs the static
        ``t_max`` rounds and masks rounds past t_c, so a per-outer-iteration
        consensus budget read from a schedule array stays inside ONE compiled
        whole-run program per mesh — this is the inner scan of the fused
        S-DOT SPMD executor (sdot.sdot_spmd). Round i < t_c applies exactly
        the same update as gossip_rounds, in the same order.
        """
        axis = self.axis
        if self._is_ring and self.n > 2:
            w_self, w_prev, w_next = self._ring_coeffs()
            fwd = [(i, (i + 1) % self.n) for i in range(self.n)]
            bwd = [(i, (i - 1) % self.n) for i in range(self.n)]

            def round_(zz, i):
                zp = jax.lax.ppermute(zz, axis, fwd)   # receives from i-1
                zn = jax.lax.ppermute(zz, axis, bwd)   # receives from i+1
                mixed = w_self * zz + w_prev * zp + w_next * zn
                return jnp.where(i < t_c, mixed, zz), None

            out, _ = jax.lax.scan(round_, z, jnp.arange(t_max))
            return out
        wj = jnp.asarray(self.weights, z.dtype)
        idx = jax.lax.axis_index(axis)

        def round_(zz, i):
            allz = jax.lax.all_gather(zz, axis)            # (N, ...)
            row = jax.lax.dynamic_slice_in_dim(wj, idx, 1, 0)[0]  # (N,)
            mixed = jnp.tensordot(row, allz, axes=(0, 0),
                                  precision=PRECISION)
            return jnp.where(i < t_c, mixed, zz), None

        out, _ = jax.lax.scan(round_, z, jnp.arange(t_max))
        return out

    def debias_table(self, t_max: int) -> jnp.ndarray:
        """Cached (t_max + 1, N) device table of [W^t e_1] rows.

        Same contract as DenseConsensus.debias_table; rows are indexed by the
        traced per-iteration budget inside the fused SPMD scan instead of a
        host matrix_power per outer iteration.
        """
        t_max = int(t_max)
        if t_max not in self._debias_tables:
            self._debias_tables[t_max] = debias_table(self._w, t_max)
        return self._debias_tables[t_max]

    def debias_by_table(self, z: jnp.ndarray, table: jnp.ndarray,
                        t_c: jnp.ndarray) -> jnp.ndarray:
        """Traceable twin of ``debias`` (inside shard_map): divide the local
        block by table[t_c][mesh position]. ``table`` must be passed in as a
        replicated shard_map operand so the row gather stays device-side."""
        idx = jax.lax.axis_index(self.axis)
        scale = jnp.take(table, t_c, axis=0)               # (N,)
        s = jax.lax.dynamic_slice_in_dim(scale, idx, 1, 0)[0]
        return z / s.astype(z.dtype)

    def debias(self, z: jnp.ndarray, t_c: int) -> jnp.ndarray:
        """Divide the local block by [W^{t_c} e_1]_i (inside shard_map)."""
        scale = jnp.asarray(debias_weights(self.weights, int(t_c)), z.dtype)
        idx = jax.lax.axis_index(self.axis)
        s = jax.lax.dynamic_slice_in_dim(scale, idx, 1, 0)[0]
        return z / s

    def build_debiased_sum(self, t_c: int):
        """Returns a jitted f(z_stacked) -> per-node approx of sum_j Z_j.

        z_stacked: (N, ...) array sharded so that axis 0 maps to the mesh
        axis. Output has the same sharding. This is the SPMD twin of
        DenseConsensus.run_debiased and is numerically identical for the
        same W (verified in tests/test_consensus_spmd.py).
        """
        mesh, axis = self.mesh, self.axis

        def local_fn(z):  # z: (1, ...) local block
            zz = z[0]
            zz = self.gossip_rounds(zz, t_c)
            zz = self.debias(zz, t_c)
            return zz[None]

        spec = P(axis)
        fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec,),
                           out_specs=spec)
        return jax.jit(fn)


def two_level_reduce(z: jnp.ndarray, *, intra_axis: str, inter: "SpmdConsensus",
                     t_c: int) -> jnp.ndarray:
    """TPU-native S-DOT consensus (DESIGN.md sec.2): exact psum over the fast
    intra-pod axis followed by t_c gossip rounds + debias over the slow
    cross-pod axis. Call inside shard_map with both axes visible."""
    z = jax.lax.psum(z, intra_axis)
    z = inter.gossip_rounds(z, t_c)
    return inter.debias(z, t_c)
