"""Main-path Pallas kernels compile for a TPU v5e at real widths.

The chip is described, not attached (``jax.experimental.topologies``), so
these compiles run on a CPU-only host and raise whatever the chip's
compiler would refuse — misaligned slices, vector gathers Mosaic cannot
lower, more VMEM than a kernel may use. Nothing runs: results and times
come only from a run on the chip (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.ell_spmm import ell_spmm_pallas
from repro.kernels.gram_update import batched_gram_apply_pallas
from repro.kernels.ops import ell_block_rows
from repro.kernels.slab_ops import (batched_slab_apply_pallas,
                                    batched_slab_tq_pallas,
                                    grid_block_apply_pallas,
                                    grid_block_tq_pallas)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no described chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


# name -> (kernel, argument shapes/dtypes, static keyword arguments)
F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32
CASES = {
    # chip_smoke phase (b), LFW row: N=20, d=2914, r=7, 13,233 samples
    # (662 per node, padded to two 512-column blocks, as with block_n=512)
    "gram_lfw": (batched_gram_apply_pallas,
                 [((20, 2914, 1024), F32), ((20, 2914, 7), F32)], {}),
    # the same row as the S-DOT stack is built on the chip: 662 columns
    # tiled once to two 384-column blocks (ops.gram_tiling)
    "gram_lfw_tiled": (batched_gram_apply_pallas,
                       [((20, 2914, 768), F32), ((20, 2914, 7), F32)],
                       {"block_n": 384}),
    # phase (a)'s ImageNet row as raw data: N=100, d=1024, r=5
    "gram_imagenet": (batched_gram_apply_pallas,
                      [((100, 1024, 512), F32), ((100, 1024, 5), F32)], {}),
    # sparse gossip at 10k nodes: N=10,240 rows, ELL width 10, K=128
    "ell_f32": (ell_spmm_pallas,
                [((10240, 10), I32), ((10240, 10), F32), ((10240,), F32),
                 ((10240, 128), F32), ((10240, 128), F32)],
                {"block_rows": 256}),
    "ell_bf16": (ell_spmm_pallas,
                 [((10240, 10), I32), ((10240, 10), F32), ((10240,), F32),
                  ((10240, 128), F32), ((10240, 128), BF16)],
                 {"block_rows": 256}),
    # a hub-heavy overlay pads ELL to its max degree: the row block shrinks
    # until the step's SMEM index tiles fit
    "ell_wide": (ell_spmm_pallas,
                 [((10240, 300), I32), ((10240, 300), F32), ((10240,), F32),
                  ((10240, 128), F32), ((10240, 128), F32)],
                 {"block_rows": ell_block_rows(300)}),
    # fig6_fdot: N=10 feature slabs of d_i=1, n=500 (padded), r=5
    "slab_tq_fig6": (batched_slab_tq_pallas,
                     [((10, 1, 512), F32), ((10, 1, 5), F32)], {}),
    "slab_apply_fig6": (batched_slab_apply_pallas,
                        [((10, 1, 512), F32), ((10, 512, 5), F32)], {}),
    # bdot_fused: d=1000 over I=3 rows, 600 samples over J=2 columns, r=5
    "grid_tq_bdot": (grid_block_tq_pallas,
                     [((3, 2, 334, 512), F32), ((3, 334, 5), F32)], {}),
    "grid_apply_bdot": (grid_block_apply_pallas,
                        [((3, 2, 334, 512), F32), ((2, 512, 5), F32)], {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    kernel, args, statics = CASES[case]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    compiled = jax.jit(functools.partial(kernel, **statics)).lower(
        *shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
