"""Chip peaks, and the operations and bytes a kernel's work needs, counted
from the problem's own shapes (not from the kernel's padded blocks), so a
roofline share reads the same work whatever implements it."""
from __future__ import annotations

__all__ = ["PEAKS", "peaks", "gram_apply_counts", "roofline_pct"]

# Per chip. Source: Google Cloud documentation, "TPU v5e" (system
# architecture): 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a chip not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/roofline.PEAKS with its source") from None


def gram_apply_counts(samples, d: int, r: int):
    """One batched gram apply, V_i = X_i (X_i^T Q_i) for every node, over
    f32 data: 2 matmuls of 2 n_i d r operations each, and X read once with
    Q read and V written once per node. ``samples`` is the n_i."""
    n_total, n_nodes = sum(samples), len(samples)
    flops = 4 * n_total * d * r
    nbytes = 4 * (n_total * d + 2 * n_nodes * d * r)
    return flops, nbytes


def roofline_pct(flops: float, nbytes: float, seconds: float,
                 device_kind: str) -> float:
    """Least time the chip could take for the work, over the time taken,
    in percent: the larger of flops / peak FLOP/s and bytes / peak B/s."""
    pk = peaks(device_kind)
    least = max(flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"])
    return 100.0 * least / seconds
