"""Pure-jnp oracles for every Pallas kernel (ground truth for tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Oracle matmuls run at full f32 on the TPU too, as the kernels do (the
# reasoning is at core/linalg.PRECISION; kernels import nothing from core).
PRECISION = jax.lax.Precision.HIGHEST

__all__ = ["gram_apply_ref", "batched_gram_apply_ref", "flash_attention_ref",
           "gram_qr_ref", "batched_slab_tq_ref", "batched_slab_apply_ref",
           "grid_block_tq_ref", "grid_block_apply_ref", "ell_spmm_ref",
           "ell_spmm_scan_ref"]


def ell_spmm_ref(ell_idx: jnp.ndarray, ell_val: jnp.ndarray,
                 diag: jnp.ndarray, z_own: jnp.ndarray,
                 z_src: jnp.ndarray) -> jnp.ndarray:
    """out[i] = diag[i] z_own[i] + sum_l val[i,l] z_src[idx[i,l]], f32.

    The gather/einsum oracle for the ELL SpMM gossip round: one big (N, L,
    K) gather then a slot-contraction einsum. z_src may be a lower-
    precision (bf16) quantization of the payload — accumulation is f32
    either way. Padded slots carry weight 0, so no masking is needed.
    """
    msgs = jnp.take(z_src, ell_idx, axis=0).astype(jnp.float32)  # (N, L, K)
    acc = diag.astype(jnp.float32)[:, None] * z_own.astype(jnp.float32)
    return acc + jnp.einsum("nl,nlk->nk", ell_val.astype(jnp.float32), msgs,
                            precision=PRECISION)


def ell_spmm_dense_ref(ell_idx: jnp.ndarray, ell_val: jnp.ndarray,
                       diag: jnp.ndarray, z_own: jnp.ndarray,
                       z_src: jnp.ndarray) -> jnp.ndarray:
    """Densifying twin of ``ell_spmm_ref``: scatters the ELL slots back to
    an (N, N) off-diagonal matrix and uses the dense matmul. For hub-heavy
    graphs the padded width L approaches N and the gather path does nearly
    dense work with far worse constants than BLAS — past the measured CPU
    crossover (L ~ N/11) the O(N L) scatter + O(N^2 K) matmul is faster.
    Padded slots self-point with weight 0, so scatter-add is exact."""
    n = diag.shape[0]
    rows = jnp.broadcast_to(jnp.arange(n)[:, None], ell_idx.shape)
    w_off = jnp.zeros((n, n), jnp.float32).at[rows, ell_idx].add(
        ell_val.astype(jnp.float32))
    acc = diag.astype(jnp.float32)[:, None] * z_own.astype(jnp.float32)
    return acc + jnp.matmul(w_off, z_src.astype(jnp.float32),
                            precision=PRECISION)


def ell_spmm_scan_ref(ell_idx: jnp.ndarray, ell_val: jnp.ndarray,
                      diag: jnp.ndarray, z_own: jnp.ndarray,
                      z_src: jnp.ndarray) -> jnp.ndarray:
    """Slot-at-a-time twin of ``ell_spmm_ref``: scans the L slot columns so
    peak memory stays O(N K) instead of O(N L K) — the fallback ops.py
    selects when the gathered message block would be large."""
    acc0 = diag.astype(jnp.float32)[:, None] * z_own.astype(jnp.float32)

    def slot(acc, inp):
        cols, w = inp                                   # (N,), (N,)
        msgs = jnp.take(z_src, cols, axis=0).astype(jnp.float32)
        return acc + w.astype(jnp.float32)[:, None] * msgs, None

    acc, _ = jax.lax.scan(slot, acc0, (ell_idx.T, ell_val.T))
    return acc


def gram_apply_ref(x: jnp.ndarray, q: jnp.ndarray, normalize: bool = True) -> jnp.ndarray:
    """V = X (X^T Q) / n  — Step 5 of Alg. 1 without materializing M = XX^T.

    x: (d, n) local data block, q: (d, r) subspace iterate -> (d, r).
    """
    acc = jnp.promote_types(x.dtype, jnp.float32)
    s = x.astype(acc).T @ q.astype(acc)            # (n, r)
    v = x.astype(acc) @ s                          # (d, r)
    if normalize:
        v = v / x.shape[1]
    return v.astype(q.dtype)


def batched_gram_apply_ref(x_stack: jnp.ndarray, q_stack: jnp.ndarray,
                           n_true: jnp.ndarray) -> jnp.ndarray:
    """V[i] = X_i (X_i^T Q_i) / n_i over stacked nodes.

    x_stack: (N, d, n) zero-padded blocks (exact: padded columns are null in
    both matmuls), q_stack: (N, d, r), n_true: (N,) real per-node sample
    counts for the normalizer. One fused einsum pair — this is also the CPU
    execution path of ops.batched_gram_apply.
    """
    acc = jnp.promote_types(x_stack.dtype, jnp.float32)
    x32 = x_stack.astype(acc)
    s = jnp.einsum("idn,idr->inr", x32, q_stack.astype(acc),
                   precision=PRECISION)
    v = jnp.einsum("idn,inr->idr", x32, s, precision=PRECISION)
    v = v / n_true.astype(acc)[:, None, None]
    return v.astype(q_stack.dtype)


def batched_slab_tq_ref(x_stack: jnp.ndarray, q_stack: jnp.ndarray) -> jnp.ndarray:
    """Z[i] = X_i^T Q_i over stacked feature slabs (F-DOT Alg. 2, step 1).

    x_stack: (N, d_max, n) zero-padded slabs, q_stack: (N, d_max, r) iterates
    padded with zero rows to match. Padding is exact: the padded rows are
    null in both operands, so they contribute nothing to the (n, r) product.
    """
    acc = jnp.promote_types(x_stack.dtype, jnp.float32)
    return jnp.einsum("idn,idr->inr", x_stack.astype(acc),
                      q_stack.astype(acc)).astype(q_stack.dtype)


def batched_slab_apply_ref(x_stack: jnp.ndarray, s_stack: jnp.ndarray) -> jnp.ndarray:
    """V[i] = X_i S_i over stacked feature slabs (F-DOT Alg. 2, step 3).

    x_stack: (N, d_max, n) zero-padded slabs, s_stack: (N, n, r) debiased
    consensus sums. Padded rows of X produce zero rows of V — exact.
    """
    acc = jnp.promote_types(x_stack.dtype, jnp.float32)
    return jnp.einsum("idn,inr->idr", x_stack.astype(acc),
                      s_stack.astype(acc)).astype(s_stack.dtype)


def grid_block_tq_ref(x_grid: jnp.ndarray, q_stack: jnp.ndarray) -> jnp.ndarray:
    """Z[i, j] = X_ij^T Q_i over an I x J grid of blocks (B-DOT stage 1).

    x_grid: (I, J, d_max, n_max) zero-padded blocks, q_stack: (I, d_max, r)
    zero-row-padded row iterates. Padded feature rows are null in both
    operands and padded sample columns of X produce zero rows of Z — exact.
    """
    acc = jnp.promote_types(x_grid.dtype, jnp.float32)
    return jnp.einsum("ijdn,idr->ijnr", x_grid.astype(acc),
                      q_stack.astype(acc)).astype(q_stack.dtype)


def grid_block_apply_ref(x_grid: jnp.ndarray, s_stack: jnp.ndarray) -> jnp.ndarray:
    """V[i, j] = X_ij S_j over an I x J grid of blocks (B-DOT stage 2).

    x_grid: (I, J, d_max, n_max) zero-padded blocks, s_stack: (J, n_max, r)
    per-column consensus sums. Padded sample columns of X multiply the padded
    (zero) rows of S and padded feature rows of X give zero rows of V — exact.
    """
    acc = jnp.promote_types(x_grid.dtype, jnp.float32)
    return jnp.einsum("ijdn,jnr->ijdr", x_grid.astype(acc),
                      s_stack.astype(acc)).astype(s_stack.dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int | None = None,
                        scale: float | None = None) -> jnp.ndarray:
    """Standard softmax attention oracle.

    q: (b, h, sq, hd), k/v: (b, h, skv, hd). ``window``: optional sliding
    window (attend to keys within [i - window + 1, i]).
    """
    acc = jnp.float32
    hd = q.shape[-1]
    scale = (hd ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(acc), k.astype(acc)) * scale
    sq, skv = q.shape[2], k.shape[2]
    qpos = jnp.arange(sq)[:, None] + (skv - sq)    # align ends (decode-friendly)
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jnp.exp(logits - logits.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(acc))
    return out.astype(q.dtype)


def gram_qr_ref(v: jnp.ndarray) -> jnp.ndarray:
    """G = V^T V in f32 (oracle for the CholeskyQR Gram kernel)."""
    acc = jnp.promote_types(v.dtype, jnp.float32)
    v32 = v.astype(acc)
    return (v32.T @ v32).astype(jnp.float32)
