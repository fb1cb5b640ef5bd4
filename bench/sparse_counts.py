"""The operations and bytes of one sparse gossip round through the ELL
kernel (``kernels/ell_spmm.ell_spmm_pallas``), counted from the problem's
own shapes: the configuration's network and the payload width d r, not
the kernel's padded ELL slots or rows.

One round computes Z' = diag(W) Z + W_off Z for an (N, K) payload: a
multiply and an add per off-diagonal entry and per diagonal entry of each
payload column. It reads the payload once and writes it once (f32), and
reads each off-diagonal weight and its column index (4 bytes each) and
the diagonal (f32) once.
"""
from __future__ import annotations

import functools
import json

from bench import spec

__all__ = ["ell_spmm_counts", "config_counts"]


def ell_spmm_counts(n: int, nnz: int, k: int):
    """(FLOPs, bytes) of one round over an (n, k) f32 payload with ``nnz``
    off-diagonal entries."""
    flops = 2 * (nnz + n) * k
    nbytes = 4 * 2 * n * k + 8 * nnz + 4 * n
    return flops, nbytes


@functools.lru_cache(maxsize=4)
def _nnz(graph: str, n: int) -> int:
    return int(spec.adjacency(json.loads(graph), n).sum())


def config_counts(config: dict):
    """(FLOPs, bytes) of one round of a configuration's S-DOT gossip: its
    network from ``bench/graphs`` (any kind), payload width d r."""
    n = config["n_nodes"]
    nnz = _nnz(json.dumps(config["graph"], sort_keys=True), n)
    return ell_spmm_counts(n, nnz, config["d"] * config["r"])
