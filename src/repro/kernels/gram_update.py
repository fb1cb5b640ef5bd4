"""Pallas TPU kernel: fused gram-apply  V = X (X^T Q).

This is the compute hot spot of S-DOT (Alg. 1, Step 5): every node applies
its local covariance M_i = X_i X_i^T / n_i to the subspace iterate Q. For
large d, materializing M_i (d x d) is HBM-hostile; the fused form streams X
through VMEM once per column-block and performs two MXU matmuls per tile:

    for each column block X_b (d x bn):   S_b = X_b^T Q   (bn x r)
                                          V  += X_b S_b   (d  x r)

Arithmetic intensity: 4*d*bn*r flops per (d*bn + d*r) * bytes moved — for
r = 128 this is comfortably compute-bound on the MXU.

Grid layout: (n_blocks,) outer sequential grid walks column blocks; the
(d x r) output block is revisited every step and accumulated in VMEM
(TPU grids are sequential, so accumulation over the grid is safe). bn is the
column tile; ops.py checks ``gram_vmem_bytes`` against ``GRAM_VMEM_LIMIT``
before it picks the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gram_apply_pallas", "batched_gram_apply_pallas",
           "gram_vmem_bytes", "GRAM_VMEM_LIMIT"]

# Full f32 on the MXU, as on the rest of the S-DOT path (the reasoning is
# at core/linalg.PRECISION; kernels import nothing from core).
PRECISION = jax.lax.Precision.HIGHEST

# Scoped VMEM the gram kernels may use (the v5e default is 16 MiB of its
# 128 MiB). Full-f32 dots keep split copies of the X block: compiled for a
# described v5e, d=2914 with 512-column blocks needs 18.3 MiB and d=8192
# with 256-column blocks 27.8 MiB.
GRAM_VMEM_LIMIT = 64 * 2**20


def gram_vmem_bytes(d: int, r: int, block_n: int) -> int:
    """Upper estimate of the gram kernels' scoped VMEM: the X block
    double-buffered plus one full-f32 split copy, and the lane-padded Q and
    V blocks double-buffered. It over-counts the compiler's own figure by
    1.3-1.5x at d=2914..8192."""
    lanes = -(-r // 128) * 128
    return (3 * d * block_n + 4 * d * lanes) * 4


def _gram_kernel(x_ref, q_ref, v_ref):
    """One grid step: accumulate X_b (X_b^T Q) into the output block."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        v_ref[...] = jnp.zeros_like(v_ref)

    x = x_ref[...]          # (d, bn)
    q = q_ref[...]          # (d, r)
    s = jax.lax.dot_general(
        x, q, (((0,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32)        # X_b^T Q: (bn, r)
    v = jax.lax.dot_general(
        x, s, (((1,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32)        # X_b S: (d, r)
    v_ref[...] += v.astype(v_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gram_apply_pallas(x: jnp.ndarray, q: jnp.ndarray, *, block_n: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """V = X (X^T Q); shapes (d, n) x (d, r) -> (d, r), n % block_n == 0.

    Call through ops.gram_apply which pads/normalizes and picks block sizes.
    """
    d, n = x.shape
    d2, r = q.shape
    assert d == d2, "x and q must share the feature dimension"
    assert n % block_n == 0, "ops.py pads n to a block multiple"
    n_blocks = n // block_n

    out = pl.pallas_call(
        _gram_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((d, block_n), lambda j: (0, j)),   # X column block
            pl.BlockSpec((d, r), lambda j: (0, 0)),         # Q (resident)
        ],
        out_specs=pl.BlockSpec((d, r), lambda j: (0, 0)),   # V (accumulated)
        out_shape=jax.ShapeDtypeStruct((d, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=GRAM_VMEM_LIMIT),
        interpret=interpret,
    )(x, q)
    return out


def _batched_gram_kernel(x_ref, q_ref, v_ref):
    """One (i, j) grid step: accumulate X_{i,b} (X_{i,b}^T Q_i) into V_i.

    The column-block index j is the fast (innermost) grid dimension, so each
    node's output block is revisited j = 0..n_blocks-1 consecutively —
    sequential TPU grids make the accumulation safe; init happens at j == 0.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        v_ref[...] = jnp.zeros_like(v_ref)

    x = x_ref[0]            # (d, bn) — node i's column block
    q = q_ref[0]            # (d, r)  — node i's iterate
    s = jax.lax.dot_general(
        x, q, (((0,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32)        # X_b^T Q: (bn, r)
    v = jax.lax.dot_general(
        x, s, (((1,), (0,)), ((), ())), precision=PRECISION,
        preferred_element_type=jnp.float32)        # X_b S: (d, r)
    v_ref[0, ...] += v.astype(v_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def batched_gram_apply_pallas(x_stack: jnp.ndarray, q_stack: jnp.ndarray, *,
                              block_n: int = 512,
                              interpret: bool = False) -> jnp.ndarray:
    """V[i] = X_i (X_i^T Q_i) for all nodes in one kernel launch.

    x_stack: (N, d, n) zero-padded node data (ragged n_i padded to a common
    n — exact, padded columns contribute X_b S_b = 0); q_stack: (N, d, r).
    Grid is (node, column-block); one launch replaces N separate gram-apply
    dispatches, which is what lets the whole S-DOT scan body stay fused.
    Call through ops.batched_gram_apply, which pads and normalizes by the
    true per-node sample counts.
    """
    n_nodes, d, n = x_stack.shape
    n2, d2, r = q_stack.shape
    assert n_nodes == n2 and d == d2, "x_stack and q_stack must align"
    assert n % block_n == 0, "ops.py pads n to a block multiple"
    n_blocks = n // block_n

    out = pl.pallas_call(
        _batched_gram_kernel,
        grid=(n_nodes, n_blocks),
        in_specs=[
            pl.BlockSpec((1, d, block_n), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, d, r), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d, r), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_nodes, d, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=GRAM_VMEM_LIMIT),
        interpret=interpret,
    )(x_stack, q_stack)
    return out
