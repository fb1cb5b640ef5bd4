"""Public jit'd wrappers around the Pallas kernels.

These handle padding to hardware-aligned tiles, GQA head expansion, CPU
fallback (interpret mode or the pure-jnp oracle), and normalization — so the
rest of the codebase never calls pallas_call directly.

Off TPU the kernels run with ``interpret=True`` (the kernel body executes in
Python against the same BlockSpec tiling the TPU would use); on TPU the
identical code lowers to Mosaic and ``interpret`` defaults to False.

Every wrapper with a VMEM guard reports its dispatch through a ``*_path``
function ('pallas' or a named fallback). On TPU a tripped guard also warns
at trace time, so a run that was meant to use a kernel never falls back to
the jnp oracle unseen.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from . import ref
from .ell_spmm import (ell_spmm_pallas, ell_spmm_smem_bytes,
                       ell_spmm_vmem_bytes)
from .flash_attention import flash_attention_pallas
from .gram_qr import gram_qr_pallas
from .gram_update import (GRAM_VMEM_LIMIT, batched_gram_apply_pallas,
                          gram_apply_pallas, gram_vmem_bytes)
from .slab_ops import (batched_slab_apply_pallas, batched_slab_tq_pallas,
                       grid_block_apply_pallas, grid_block_tq_pallas)

__all__ = ["gram_apply", "batched_gram_apply", "batched_slab_tq",
           "batched_slab_apply", "grid_block_tq", "grid_block_apply",
           "gram_qr", "flash_attention", "ell_spmm", "ell_spmm_path",
           "ell_block_rows", "batched_gram_apply_path",
           "gram_tiling", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Per-kernel VMEM guard for the blocks of the slab and grid kernels: one
# block set, not counting double buffering.
_VMEM_GUARD = 8 * 2**20

# Double-buffered VMEM the ELL kernel may claim. Compiled for a described
# v5e, its pipeline was accepted at 45.6 MB (N=44,000, K=128, f32) and
# refused at 49.8 MB (N=48,000); 40 MiB keeps a margin below that.
_ELL_VMEM_BUDGET = 40 * 2**20

# SMEM (1 MiB on v5e) the ELL kernel's index tiles may claim. Compiled for a
# described v5e, 768 KiB (L=128, 256-row blocks) and 896 KiB (L=300,
# 128-row blocks) were accepted and 1.25 MiB (L=129, 256 rows) refused.
_ELL_SMEM_BUDGET = 960 * 2**10


def _guarded_path(kernel: str, use_pallas: bool | None, need_bytes: int,
                  budget: int = _VMEM_GUARD, memory: str = "VMEM") -> str:
    """'pallas' | 'fallback_ref' (kernel off) | 'fallback_vmem' (guard
    tripped). A tripped guard on TPU warns: the oracle then runs in place
    of the kernel the caller asked for."""
    if use_pallas is None:
        use_pallas = on_tpu()
    if not use_pallas:
        return "fallback_ref"
    if need_bytes <= budget:
        return "pallas"
    if on_tpu():
        warnings.warn(f"{kernel}: {need_bytes} B of {memory} blocks exceed "
                      f"the {budget} B guard; running the jnp oracle instead "
                      f"of the Pallas kernel", stacklevel=3)
    return "fallback_vmem"


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_n", "use_pallas", "interpret"))
def gram_apply(x: jnp.ndarray, q: jnp.ndarray, *, block_n: int = 512,
               use_pallas: bool = True, interpret: bool | None = None) -> jnp.ndarray:
    """V = X (X^T Q) / n. x: (d, n), q: (d, r) -> (d, r).

    Zero-padding n is exact (padded columns contribute X_b S_b = 0); the
    normalizer uses the true n.
    """
    d, n = x.shape
    if _guarded_path("gram_apply", use_pallas,
                     gram_vmem_bytes(d, q.shape[-1], block_n),
                     GRAM_VMEM_LIMIT) != "pallas":
        return ref.gram_apply_ref(x, q)
    interp = (not on_tpu()) if interpret is None else interpret
    xp = _pad_to(x, 1, block_n)
    v = gram_apply_pallas(xp, q, block_n=block_n, interpret=interp)
    return (v / n).astype(q.dtype)


def gram_tiling(n: int) -> tuple[int, int]:
    """``(width, block_n)``: how the batched gram kernel tiles ``n``
    sample columns. The fewest column blocks of at most 512 that hold n
    rounded up to 128 lanes, each as narrow as a multiple of 128 allows,
    so fewer than 128 zero columns a block pad n (662 -> 2 x 384 = 768,
    not 1,024). A stack built at ``width`` tiles to itself: its
    ``block_n`` divides it and nothing is padded."""
    blocks = -(-n // 512)
    per_block = -(-n // blocks)
    block_n = -(-per_block // 128) * 128
    return blocks * block_n, block_n


def batched_gram_apply_path(d: int, r: int, block_n: int = 512,
                            use_pallas: bool | None = None) -> str:
    """Which path ``batched_gram_apply`` takes for (d, r): 'pallas' |
    'fallback_ref' | 'fallback_vmem' (host-side mirror of its dispatch)."""
    return _guarded_path("batched_gram_apply", use_pallas,
                         gram_vmem_bytes(d, r, block_n), GRAM_VMEM_LIMIT)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "use_pallas", "interpret"))
def batched_gram_apply(x_stack: jnp.ndarray, q_stack: jnp.ndarray,
                       n_true: jnp.ndarray, *, block_n: int | None = None,
                       use_pallas: bool | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """V[i] = X_i (X_i^T Q_i) / n_i — batched Step 5 for all nodes at once.

    x_stack: (N, d, n) zero-padded blocks, q_stack: (N, d, r), n_true: (N,)
    true per-node sample counts (zero-padding is exact; the normalizer uses
    n_true). This is the dispatch point for the fused S-DOT executor's raw-
    data path: one call per outer iteration regardless of N.

    ``block_n=None`` takes ``gram_tiling(n)``'s block. A stack already
    built at that width (``sdot._stack_data`` builds it so on the kernel's
    path) goes to the kernel as it is; any other is zero-padded here to a
    multiple of the block, on every call.

    ``use_pallas=None`` auto-selects: the Pallas (node, column-block) kernel
    on TPU, the fused-einsum oracle elsewhere (interpret-mode Pallas unrolls
    the grid at trace time, which bloats the fused scan's XLA program on
    CPU for no speed win). Pass use_pallas=True + interpret=True in tests to
    exercise the kernel itself off-TPU.
    """
    n_nodes, d, n = x_stack.shape
    if block_n is None:
        block_n = gram_tiling(n)[1]
    if batched_gram_apply_path(d, q_stack.shape[-1], block_n,
                               use_pallas) != "pallas":
        return ref.batched_gram_apply_ref(x_stack, q_stack, n_true)
    interp = (not on_tpu()) if interpret is None else interpret
    xp = _pad_to(x_stack, 2, block_n)
    v = batched_gram_apply_pallas(xp, q_stack, block_n=block_n,
                                  interpret=interp)
    acc = v.dtype
    v = v / n_true.astype(acc)[:, None, None]
    return v.astype(q_stack.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "use_pallas", "interpret"))
def batched_slab_tq(x_stack: jnp.ndarray, q_stack: jnp.ndarray, *,
                    block_n: int = 512, use_pallas: bool | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Z[i] = X_i^T Q_i — batched F-DOT step 1 for all nodes at once.

    x_stack: (N, d_max, n) zero-padded feature slabs, q_stack: (N, d_max, r)
    zero-row-padded iterates (padding exact in the product). This is the
    dispatch point for the fused F-DOT executor's partial-product step.

    ``use_pallas=None`` auto-selects: the Pallas (node, sample-block) kernel
    on TPU, the fused-einsum oracle elsewhere (same rationale as
    batched_gram_apply — interpret-mode Pallas unrolls the grid at trace
    time, bloating the fused scan's XLA program on CPU for no win).
    """
    n_nodes, d, n = x_stack.shape
    r = q_stack.shape[-1]
    vmem_bytes = (d * block_n + d * r + block_n * r) * 4
    if _guarded_path("batched_slab_tq", use_pallas, vmem_bytes) != "pallas":
        return ref.batched_slab_tq_ref(x_stack, q_stack)
    interp = (not on_tpu()) if interpret is None else interpret
    xp = _pad_to(x_stack, 2, block_n)
    z = batched_slab_tq_pallas(xp, q_stack, block_n=block_n, interpret=interp)
    return z[:, :n].astype(q_stack.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "use_pallas", "interpret"))
def batched_slab_apply(x_stack: jnp.ndarray, s_stack: jnp.ndarray, *,
                       block_n: int = 512, use_pallas: bool | None = None,
                       interpret: bool | None = None) -> jnp.ndarray:
    """V[i] = X_i S_i — batched F-DOT step 3 for all nodes at once.

    x_stack: (N, d_max, n) zero-padded feature slabs, s_stack: (N, n, r)
    debiased consensus sums. The sample axis of both operands is padded
    together, so padded columns of X multiply zero rows of S — exact.
    """
    n_nodes, d, n = x_stack.shape
    r = s_stack.shape[-1]
    vmem_bytes = (d * block_n + block_n * r + d * r) * 4
    if _guarded_path("batched_slab_apply", use_pallas,
                     vmem_bytes) != "pallas":
        return ref.batched_slab_apply_ref(x_stack, s_stack)
    interp = (not on_tpu()) if interpret is None else interpret
    xp = _pad_to(x_stack, 2, block_n)
    sp = _pad_to(s_stack, 1, block_n)
    v = batched_slab_apply_pallas(xp, sp, block_n=block_n, interpret=interp)
    return v.astype(s_stack.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "use_pallas", "interpret"))
def grid_block_tq(x_grid: jnp.ndarray, q_stack: jnp.ndarray, *,
                  block_n: int = 512, use_pallas: bool | None = None,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Z[i, j] = X_ij^T Q_i — batched B-DOT stage 1 for the whole grid.

    x_grid: (I, J, d_max, n_max) zero-padded blocks, q_stack: (I, d_max, r)
    zero-row-padded row iterates (padding exact in the product). This is the
    dispatch point for the fused B-DOT executor's column-partial step.

    ``use_pallas=None`` auto-selects: the Pallas (row, column, sample-block)
    kernel on TPU, the fused-einsum oracle elsewhere (interpret-mode Pallas
    unrolls the grid at trace time, bloating the fused scan's XLA program on
    CPU for no win — same rationale as batched_slab_tq).
    """
    i_rows, j_cols, d, n = x_grid.shape
    r = q_stack.shape[-1]
    vmem_bytes = (d * block_n + d * r + block_n * r) * 4
    if _guarded_path("grid_block_tq", use_pallas, vmem_bytes) != "pallas":
        return ref.grid_block_tq_ref(x_grid, q_stack)
    interp = (not on_tpu()) if interpret is None else interpret
    xp = _pad_to(x_grid, 3, block_n)
    z = grid_block_tq_pallas(xp, q_stack, block_n=block_n, interpret=interp)
    return z[:, :, :n].astype(q_stack.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "use_pallas", "interpret"))
def grid_block_apply(x_grid: jnp.ndarray, s_stack: jnp.ndarray, *,
                     block_n: int = 512, use_pallas: bool | None = None,
                     interpret: bool | None = None) -> jnp.ndarray:
    """V[i, j] = X_ij S_j — batched B-DOT stage 2 for the whole grid.

    x_grid: (I, J, d_max, n_max) zero-padded blocks, s_stack: (J, n_max, r)
    per-column debiased consensus sums. The sample axis of both operands is
    padded together, so padded columns of X multiply zero rows of S — exact.
    """
    i_rows, j_cols, d, n = x_grid.shape
    r = s_stack.shape[-1]
    vmem_bytes = (d * block_n + block_n * r + d * r) * 4
    if _guarded_path("grid_block_apply", use_pallas,
                     vmem_bytes) != "pallas":
        return ref.grid_block_apply_ref(x_grid, s_stack)
    interp = (not on_tpu()) if interpret is None else interpret
    xp = _pad_to(x_grid, 3, block_n)
    sp = _pad_to(s_stack, 1, block_n)
    v = grid_block_apply_pallas(xp, sp, block_n=block_n, interpret=interp)
    return v.astype(s_stack.dtype)


# Above this many gathered message elements (N * L * K) the one-shot
# gather/einsum fallback's (N, L, K) intermediate is worth trading for the
# slot-at-a-time scan's O(N K) peak memory.
_ELL_GATHER_ELEMS = 1 << 25


def ell_block_rows(ell_width: int, block_rows: int = 256) -> int:
    """Rows per ELL kernel step: ``block_rows`` halved until the step's
    SMEM index tiles fit (0 when even 8 rows do not)."""
    while (block_rows >= 8 and ell_spmm_smem_bytes(block_rows, ell_width)
           > _ELL_SMEM_BUDGET):
        block_rows //= 2
    return block_rows if block_rows >= 8 else 0


def ell_spmm_path(n: int, ell_width: int, k: int,
                  use_pallas: bool | None = None,
                  payload_dtype: str | None = None,
                  block_rows: int = 256) -> str:
    """Which execution path ``ell_spmm`` will take for these shapes:
    'pallas' | 'fallback_gather' | 'fallback_scan'
    (host-side mirror of the traced dispatch below, for observability and
    benchmarks). The Pallas kernel keeps the whole gather source resident
    and double-buffered, at 2 bytes/elem in bf16 payload mode, and runs
    with ``ell_block_rows`` rows per step."""
    itemsize = 2 if payload_dtype == "bfloat16" else 4
    rows = ell_block_rows(ell_width, block_rows)
    guard = ((ell_spmm_vmem_bytes(n, k, rows, itemsize), _ELL_VMEM_BUDGET,
              "VMEM") if rows else
             (ell_spmm_smem_bytes(8, ell_width), _ELL_SMEM_BUDGET, "SMEM"))
    if _guarded_path("ell_spmm", use_pallas, *guard) == "pallas":
        return "pallas"
    if n * ell_width * k <= _ELL_GATHER_ELEMS:
        return "fallback_gather"
    return "fallback_scan"


@functools.partial(jax.jit,
                   static_argnames=("payload_dtype", "block_rows",
                                    "use_pallas", "interpret"))
def ell_spmm(ell_idx: jnp.ndarray, ell_val: jnp.ndarray, diag: jnp.ndarray,
             z: jnp.ndarray, *, payload_dtype: str | None = None,
             block_rows: int = 256, use_pallas: bool | None = None,
             interpret: bool | None = None) -> jnp.ndarray:
    """One sparse gossip round: out[i] = diag[i] z[i] + sum_l val[i,l]
    z[idx[i,l]]. ell_idx/ell_val: (N, L) padded ELL slots (weight 0 past
    the row degree), diag: (N,), z: (N, K) flattened payload -> (N, K)
    f32.

    ``payload_dtype`` (e.g. "bfloat16") quantizes the GATHER SOURCE — the
    neighbor messages that cross the wire — before the f32 accumulation;
    each node's own diagonal term stays full precision.

    ``use_pallas=None`` auto-selects: the Pallas row-block gather kernel
    on TPU (guarded by the double-buffered payload fitting VMEM), the
    gather/einsum oracle elsewhere, degrading to a slot-at-a-time scan
    when the (N, L, K) gathered block would be large (see
    ``ell_spmm_path``).
    """
    n, k = z.shape
    ell_width = ell_idx.shape[1]
    z_src = z if payload_dtype is None else z.astype(payload_dtype)
    path = ell_spmm_path(n, ell_width, k, use_pallas, payload_dtype,
                         block_rows)
    if path == "fallback_gather":
        return ref.ell_spmm_ref(ell_idx, ell_val, diag, z, z_src)
    if path == "fallback_scan":
        return ref.ell_spmm_scan_ref(ell_idx, ell_val, diag, z, z_src)
    interp = (not on_tpu()) if interpret is None else interpret
    block_rows = ell_block_rows(ell_width, block_rows)
    idx_p = _pad_to(ell_idx, 0, block_rows)
    val_p = _pad_to(ell_val, 0, block_rows)
    diag_p = _pad_to(diag, 0, block_rows)
    z_p = _pad_to(z, 0, block_rows)
    out = ell_spmm_pallas(idx_p, val_p, diag_p, z_p, z_src,
                          block_rows=block_rows, interpret=interp)
    return out[:n]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "use_pallas", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    use_pallas: bool = True,
                    interpret: bool | None = None) -> jnp.ndarray:
    """GQA-aware attention. q: (b, hq, sq, hd); k/v: (b, hkv, skv, hd).

    hq % hkv == 0; kv heads are expanded to query heads before the kernel
    (on real TPU the broadcast is free — the expanded operand is an HLO
    broadcast the partitioner keeps unmaterialized per-shard).
    """
    b, hq, sq, hd = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, "query heads must be a multiple of kv heads"
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)

    small = sq < block_q or skv < block_k
    if not use_pallas or small:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)

    interp = (not on_tpu()) if interpret is None else interpret
    # back-pad both streams; real positions are communicated to the kernel
    # via q_offset (first real query's position in the key stream) and
    # kv_valid (number of real keys), so padding never leaks into the mask.
    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    out = flash_attention_pallas(
        qp, kp, vp, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        q_offset=skv - sq, kv_valid=skv, interpret=interp)
    return out[:, :, :sq, :]


@functools.partial(jax.jit, static_argnames=("block_d", "use_pallas", "interpret"))
def gram_qr(v: jnp.ndarray, *, block_d: int = 1024, use_pallas: bool = True,
            interpret: bool | None = None) -> jnp.ndarray:
    """G = V^T V. v: (d, r) -> (r, r) f32. Zero-padding d is exact."""
    d, r = v.shape
    if not use_pallas or d < block_d:
        return ref.gram_qr_ref(v)
    interp = (not on_tpu()) if interpret is None else interpret
    vp = _pad_to(v, 0, block_d)
    return gram_qr_pallas(vp, block_d=block_d, interpret=interp)
