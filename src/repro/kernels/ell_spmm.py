"""Pallas TPU kernel: ELL sparse-matrix x dense-block gossip round.

One gossip round on a sparse topology is

    out[i] = diag[i] * z[i] + sum_l val[i, l] * z[idx[i, l]]

with (idx, val) the padded-ELL neighbor slots of ``core/sparse.SparseW``
(slots past the row's degree self-point with weight 0, so no masking is
needed inside the multiply-accumulate) and z the (N, K) flattened gossip
payload. The grid tiles the ROW axis; each step owns its (block_rows, K)
output tile:

* the step's (block_rows, L) index/weight tiles and (block_rows, 1)
  diagonal tile land in SMEM, so every slot is a scalar read (the whole
  (N, L) table does not fit SMEM at 1k-10k nodes; one block's tile does);
* the FULL payload stays resident in VMEM as a second input block and
  each output row is a loop over its L slots, each loading ONE payload
  row at the scalar index — Mosaic lowers a dynamic (1, K) row slice,
  where a vector gather (``jnp.take`` on a row vector) does not compile;
* accumulation is f32. The bf16 payload mode gathers from a bf16 source
  packed two rows per uint32 word (``pack_bf16_rows``): Mosaic cannot
  load a single bf16 row at an odd offset, but it can load one packed
  word row and widen the wanted half to f32 exactly (bf16 -> f32 is a
  16-bit shift), so the source stays at 2 bytes per element in VMEM.

The ELL width L is a static Python int (single digits on the sparse
topologies this targets), so the slot loop unrolls at trace time; the
row loop is a ``fori_loop``. Call through ``ops.ell_spmm``, which pads
rows to a block multiple, guards the VMEM footprint, and shrinks the row
block until the SMEM tiles fit (wide ELL on hub-heavy graphs).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ell_spmm_pallas", "ell_spmm_smem_bytes", "ell_spmm_vmem_bytes",
           "pack_bf16_rows"]


def pack_bf16_rows(z: jnp.ndarray) -> jnp.ndarray:
    """(N, K) bf16 -> (ceil(N/2), K) uint32: row 2m in the low half-word,
    row 2m+1 in the high half-word of the same column."""
    n, k = z.shape
    z = jnp.pad(z, ((0, n % 2), (0, 0)))
    pairs = z.reshape(-1, 2, k).transpose(0, 2, 1)           # (N/2, K, 2)
    return jax.lax.bitcast_convert_type(pairs, jnp.uint32)


def _packed_row(src_ref, j):
    """Row j of a ``pack_bf16_rows`` source, widened exactly to f32."""
    word = src_ref[pl.ds(j // 2, 1), :]                      # (1, K) uint32
    bits = jnp.where(j % 2 == 0, word << 16, word & jnp.uint32(0xFFFF0000))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _ell_spmm_kernel(idx_ref, val_ref, diag_ref, zrow_ref, src_ref, o_ref,
                     *, ell_width: int, block_rows: int, packed: bool):
    """One row-block grid step: per-row slot loop of scalar-indexed loads."""

    def gather(j):
        if packed:
            return _packed_row(src_ref, j)
        return src_ref[pl.ds(j, 1), :].astype(jnp.float32)

    def row(i, carry):
        acc = diag_ref[i, 0] * zrow_ref[pl.ds(i, 1), :].astype(jnp.float32)
        for l in range(ell_width):                           # static unroll
            acc = acc + val_ref[i, l] * gather(idx_ref[i, l])
        o_ref[pl.ds(i, 1), :] = acc
        return carry

    jax.lax.fori_loop(0, block_rows, row, 0)


def ell_spmm_vmem_bytes(n_src: int, k: int, block_rows: int,
                        src_itemsize: int) -> int:
    """VMEM the kernel's pipeline allocates: the resident gather source
    and the own-state/output row tiles, each double-buffered."""
    return 2 * (n_src * k * src_itemsize + 2 * block_rows * k * 4)


def ell_spmm_smem_bytes(block_rows: int, ell_width: int) -> int:
    """SMEM the kernel's pipeline allocates: the (block_rows, L) index and
    weight tiles and the (block_rows, 1) diagonal tile, each row padded to
    128 words, double-buffered."""
    lanes = -(-ell_width // 128) * 128
    return 2 * 4 * block_rows * (2 * lanes + 128)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret"))
def ell_spmm_pallas(ell_idx: jnp.ndarray, ell_val: jnp.ndarray,
                    diag: jnp.ndarray, z_own: jnp.ndarray,
                    z_src: jnp.ndarray, *, block_rows: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """out = diag*z_own + ELL-gather-sum over z_src, f32.

    ell_idx/ell_val: (Np, L) with Np % block_rows == 0 (ops.py pads rows
    with weight-0 self-pointing slots and diag 0, so padded output rows
    are exactly zero and slicing them off is exact). diag: (Np,). z_own:
    (Np, K) the row-aligned payload; z_src: (N, K) the gather source —
    bf16 in payload-quantized mode (packed in here), otherwise the same
    array as z_own's first N rows.
    """
    n_pad, ell_width = ell_idx.shape
    k = z_src.shape[1]
    assert n_pad % block_rows == 0, "ops.py pads rows to a block multiple"
    packed = z_src.dtype == jnp.bfloat16
    src = pack_bf16_rows(z_src) if packed else z_src
    smem = pltpu.MemorySpace.SMEM

    return pl.pallas_call(
        functools.partial(_ell_spmm_kernel, ell_width=ell_width,
                          block_rows=block_rows, packed=packed),
        grid=(n_pad // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, ell_width), lambda i: (i, 0),
                         memory_space=smem),
            pl.BlockSpec((block_rows, ell_width), lambda i: (i, 0),
                         memory_space=smem),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0),
                         memory_space=smem),
            pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
            pl.BlockSpec(src.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k), jnp.float32),
        interpret=interpret,
    )(ell_idx.astype(jnp.int32), ell_val.astype(jnp.float32),
      diag.astype(jnp.float32)[:, None], z_own, src)
