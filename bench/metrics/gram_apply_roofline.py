"""Share of its roofline that the Pallas gram-apply kernel reaches, in
percent: the least time the chip needs for one call's work, counted from
the problem's own shapes (``bench/roofline.gram_apply_counts``, true n_i,
no padding), over the measured time per call."""
from bench import data, roofline, trace


def read(view):
    if not view.reduced.devices:
        return None
    ns, calls = trace.op_time(view.reduced.devices[0],
                              "batched_gram_apply_pallas")
    if not calls:
        return None
    cfg = view.config
    flops, nbytes = roofline.gram_apply_counts(
        data.split_sizes(cfg["samples"], cfg["n_nodes"]), cfg["d"], cfg["r"])
    return roofline.roofline_pct(flops, nbytes, ns / 1e9 / calls,
                                 view.device_kind)
