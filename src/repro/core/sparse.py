"""Sparse mixing weights for large gossip networks (padded ELL + CSR).

Every consensus path in this repo historically multiplied a dense (N, N)
mixing matrix per gossip round — O(N^2 k) flops and O(N^2) bytes touched
per round — which caps practical simulations at N ~ 200 nodes. The
overlay topologies the paper's tradeoffs are about (Erdos-Renyi at the
connectivity threshold, small-world, scale-free, geometric) have O(N)
edges at the 1k-10k-node scale, so the mixing matrix is >99% zeros.
``SparseW`` stores exactly the nonzero structure:

* **padded ELL form** — ``ell_idx``/``ell_val``: (N, L) with L = max row
  degree. Slot (i, l) holds node i's l-th neighbor (ascending index);
  slots past ``row_nnz[i]`` self-point with weight 0, so every row does
  identical work and no raggedness leaks into ``lax.scan``. The diagonal
  is a separate (N,) vector — fault models return dropped mass to it
  without touching the off-diagonal storage.
* **CSR view** (``csr()``) — host indptr/indices/data, the interchange
  format for external tooling; ``to_dense()`` is the round-trip oracle
  the equivalence tests pin against.

``SparseW`` is a registered pytree: it flows through ``jax.jit``
arguments, scan carries, ``vmap`` (B-DOT's stacked per-subnetwork
engines) and the runtime ``Program`` operand tuple exactly like the
dense array it replaces. One gossip round is ``mix(z)``, dispatched to
the Pallas ELL-SpMM kernel on TPU and a gather/einsum fallback elsewhere
(``kernels/ops.ell_spmm``); the dense einsum engine remains the
correctness oracle.

Mixed precision: ``payload_dtype="bfloat16"`` models bf16 gossip
payloads — neighbor messages (the bytes that cross the wire) are
quantized to bf16 before the f32 accumulation, while each node's own
state stays full precision. The comm ledger prices the halved bytes via
``payload_bytes_per_elem``.

Symmetry is REQUIRED (and checked at construction): the debias table
recursion uses W^T = W, and every weight rule in ``core/topology``
(local-degree, Metropolis) is symmetric.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops

__all__ = ["SparseW", "auto_sparse"]

# Auto-selection policy for DenseConsensus(sparse=None): sparse mixing
# only ever kicks in ABOVE the network sizes the paper's table
# reproductions (and this repo's seeded test suite) run at, so every
# existing N <= 200 result keeps the dense einsum bit for bit.
AUTO_MIN_NODES = 256
AUTO_MAX_DENSITY = 0.05


def auto_sparse(n_nodes: int, density: float,
                sparse: Optional[bool] = None) -> bool:
    """Resolve the engine-level ``sparse`` tri-state.

    ``True``/``False`` are explicit; ``None`` auto-enables when the
    network is both large (>= AUTO_MIN_NODES) and sparse
    (<= AUTO_MAX_DENSITY off-diagonal density).
    """
    if sparse is not None:
        return bool(sparse)
    return n_nodes >= AUTO_MIN_NODES and density <= AUTO_MAX_DENSITY


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SparseW:
    """Symmetric doubly-stochastic mixing matrix in padded-ELL form."""

    ell_idx: jnp.ndarray      # (N, L) int32 neighbor indices (self past nnz)
    ell_val: jnp.ndarray      # (N, L) off-diagonal weights (0 past nnz)
    diag: jnp.ndarray         # (N,)   diagonal weights
    row_nnz: jnp.ndarray      # (N,)   int32 true neighbor count per row
    n: int                    # static: node count
    ell_width: int            # static: L (max row degree, >= 1)
    payload_dtype: Optional[str] = None   # static: e.g. "bfloat16"

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return ((self.ell_idx, self.ell_val, self.diag, self.row_nnz),
                (self.n, self.ell_width, self.payload_dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_dense(cls, w: np.ndarray, adjacency: Optional[np.ndarray] = None,
                   *, payload_dtype: Optional[str] = None) -> "SparseW":
        """Build from a host (N, N) weight matrix (symmetric, e.g. the
        local-degree or Metropolis construction).

        ``adjacency`` fixes the stored structure (a real edge is kept even
        if its weight happens to be 0, so fault-model send accounting
        matches the dense engine); without it the structure is the nonzero
        off-diagonal pattern of ``w``.
        """
        w = np.asarray(w, np.float64)
        n = int(w.shape[0])
        if w.shape != (n, n):
            raise ValueError(f"w must be square, got {w.shape}")
        if not np.allclose(w, w.T, atol=1e-12):
            raise ValueError("SparseW requires a symmetric weight matrix "
                             "(the debias recursion uses W^T = W)")
        if adjacency is not None:
            struct = np.asarray(adjacency) > 0
        else:
            struct = w != 0.0
        struct = np.array(struct, bool, copy=True)
        np.fill_diagonal(struct, False)
        struct |= struct.T
        row_nnz = struct.sum(axis=1).astype(np.int32)
        ell_width = max(int(row_nnz.max(initial=0)), 1)
        # row-major nonzero scan -> per-row slots in ascending neighbor order
        rows, cols = np.nonzero(struct)
        indptr = np.zeros(n + 1, np.int64)
        np.cumsum(row_nnz, out=indptr[1:])
        slots = np.arange(rows.size) - indptr[rows]
        ell_idx = np.tile(np.arange(n, dtype=np.int32)[:, None],
                          (1, ell_width))
        ell_val = np.zeros((n, ell_width), np.float32)
        ell_idx[rows, slots] = cols.astype(np.int32)
        ell_val[rows, slots] = w[rows, cols].astype(np.float32)
        return cls(jnp.asarray(ell_idx), jnp.asarray(ell_val),
                   jnp.asarray(np.diagonal(w).astype(np.float32)),
                   jnp.asarray(row_nnz), n, ell_width, payload_dtype)

    @classmethod
    def from_graph(cls, graph, weights: Optional[np.ndarray] = None, *,
                   payload_dtype: Optional[str] = None) -> "SparseW":
        """Build from a ``topology.Graph`` (default: local-degree weights)."""
        if weights is None:
            from .topology import local_degree_weights
            weights = local_degree_weights(graph)
        return cls.from_dense(weights, graph.adjacency,
                              payload_dtype=payload_dtype)

    @classmethod
    def stack(cls, sws: Sequence["SparseW"]) -> "SparseW":
        """Stack same-N engines into one batched SparseW (leading axis on
        every child), padding ELL widths to the common max — the sparse
        twin of ``jnp.stack([e._w for e in engines])`` that B-DOT's
        vmapped per-subnetwork gossip uses."""
        sws = list(sws)
        n = sws[0].n
        pd = sws[0].payload_dtype
        if any(s.n != n or s.payload_dtype != pd for s in sws):
            raise ValueError("stack needs matching n and payload_dtype")
        width = max(s.ell_width for s in sws)

        def widen(s: "SparseW"):
            extra = width - s.ell_width
            if extra == 0:
                return s.ell_idx, s.ell_val
            selfp = jnp.tile(jnp.arange(n, dtype=jnp.int32)[:, None],
                             (1, extra))
            return (jnp.concatenate([s.ell_idx, selfp], axis=1),
                    jnp.pad(s.ell_val, ((0, 0), (0, extra))))

        idx, val = zip(*(widen(s) for s in sws))
        return cls(jnp.stack(idx), jnp.stack(val),
                   jnp.stack([s.diag for s in sws]),
                   jnp.stack([s.row_nnz for s in sws]), n, width, pd)

    def __getitem__(self, k) -> "SparseW":
        """Index the leading batch axis of a ``stack``-ed SparseW."""
        return SparseW(self.ell_idx[k], self.ell_val[k], self.diag[k],
                       self.row_nnz[k], self.n, self.ell_width,
                       self.payload_dtype)

    # -- array-protocol shims (the surface consensus.py relies on) ----------
    @property
    def shape(self) -> Tuple[int, int]:
        return (self.n, self.n)

    @property
    def dtype(self):
        return self.ell_val.dtype

    def astype(self, dtype) -> "SparseW":
        """Cast the stored weights (structure untouched) — the gossip
        seams call ``w.astype(z.dtype)`` before mixing."""
        if dtype == self.ell_val.dtype:
            return self
        return SparseW(self.ell_idx, self.ell_val.astype(dtype),
                       self.diag.astype(dtype), self.row_nnz, self.n,
                       self.ell_width, self.payload_dtype)

    @property
    def T(self) -> "SparseW":
        """W^T == W: symmetry is enforced at construction."""
        return self

    def with_payload_dtype(self, payload_dtype: Optional[str]) -> "SparseW":
        return SparseW(self.ell_idx, self.ell_val, self.diag, self.row_nnz,
                       self.n, self.ell_width, payload_dtype)

    # -- the gossip round ---------------------------------------------------
    def mix(self, z: jnp.ndarray, *, use_pallas: Optional[bool] = None,
            interpret: Optional[bool] = None) -> jnp.ndarray:
        """One gossip application ``out_i = diag_i z_i + sum_l val_il
        z_{idx_il}`` over an arbitrary payload z: (N, ...). f32
        accumulation; bf16 payload quantization when ``payload_dtype`` is
        set. Traceable — this is the inner op of every fused executor's
        scan when the engine is sparse. The round runs under the device
        scope ``gossip.ell_spmm``, inside the caller's (``sdot.gossip`` in
        S-DOT's outer body)."""
        zf = z.reshape(self.n, -1)
        with jax.named_scope("gossip.ell_spmm"):
            out = kops.ell_spmm(self.ell_idx, self.ell_val, self.diag, zf,
                                payload_dtype=self.payload_dtype,
                                use_pallas=use_pallas, interpret=interpret)
        return out.astype(z.dtype).reshape(z.shape)

    def offdiag_mix(self, diag: jnp.ndarray, val: jnp.ndarray,
                    z: jnp.ndarray) -> jnp.ndarray:
        """Mixing round with OVERRIDDEN per-round diagonal and slot values
        (same structure): the fault models renormalize every realized
        round by masking ``ell_val`` and returning dropped mass to the
        diagonal, then mix through this hook."""
        zf = z.reshape(self.n, -1)
        with jax.named_scope("gossip.ell_spmm"):
            out = kops.ell_spmm(self.ell_idx, val, diag, zf,
                                payload_dtype=self.payload_dtype)
        return out.astype(z.dtype).reshape(z.shape)

    def kernel_path(self, k: int) -> str:
        """The path ``ops.ell_spmm`` takes for one of this matrix's rounds
        over a payload of ``k`` columns (``ops.ell_spmm_path``, the
        host-side mirror of its traced dispatch): 'pallas' or a fallback.
        The kernel holds the whole payload in VMEM, so the answer depends
        on the width mixed."""
        return kops.ell_spmm_path(self.n, self.ell_width, k,
                                  payload_dtype=self.payload_dtype)

    # -- stats / views (host-side) ------------------------------------------
    @property
    def nnz(self) -> int:
        """Stored entries (off-diagonal edges + the N diagonal entries)."""
        return int(np.asarray(self.row_nnz).sum()) + self.n

    @property
    def density(self) -> float:
        return self.nnz / float(self.n * self.n)

    def row_stats(self) -> dict:
        nnz = np.asarray(self.row_nnz)
        return {"n": self.n, "ell_width": self.ell_width,
                "nnz": self.nnz, "density": self.density,
                "row_nnz_min": int(nnz.min()), "row_nnz_max": int(nnz.max()),
                "row_nnz_mean": float(nnz.mean())}

    def csr(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Host CSR view (indptr, indices, data) of the off-diagonal part
        (rows in ascending-neighbor order, matching the ELL slots)."""
        idx = np.asarray(self.ell_idx)
        val = np.asarray(self.ell_val)
        nnz = np.asarray(self.row_nnz)
        keep = np.arange(self.ell_width)[None, :] < nnz[:, None]
        indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(nnz, out=indptr[1:])
        return indptr, idx[keep].astype(np.int64), val[keep]

    def to_dense(self) -> jnp.ndarray:
        """Dense (N, N) round-trip oracle (padded slots add 0 on the
        diagonal, so no masking is needed)."""
        rows = jnp.broadcast_to(
            jnp.arange(self.n, dtype=jnp.int32)[:, None],
            (self.n, self.ell_width))
        dense = jnp.zeros((self.n, self.n), self.ell_val.dtype)
        dense = dense.at[rows, self.ell_idx].add(self.ell_val)
        ar = jnp.arange(self.n)
        return dense.at[ar, ar].add(self.diag)

    def mix_host(self, x: np.ndarray) -> np.ndarray:
        """NumPy matvec/matmat (host): the oracle for power-iteration
        spectral estimates without materializing the dense matrix."""
        x = np.asarray(x)
        idx = np.asarray(self.ell_idx)
        val = np.asarray(self.ell_val)
        diag = np.asarray(self.diag)
        gathered = x[idx]                       # (N, L) or (N, L, K)
        if x.ndim == 1:
            return diag * x + (val * gathered).sum(axis=1)
        return diag[:, None] * x + (val[..., None] * gathered).sum(axis=1)

    def spectral_gap(self, iters: int = 1000, seed: int = 0) -> float:
        """1 - |lambda_2(W)| via deflated power iteration (O(nnz)/iter)."""
        from .topology import power_iteration_gap
        return power_iteration_gap(self.mix_host, self.n, iters=iters,
                                   seed=seed)
