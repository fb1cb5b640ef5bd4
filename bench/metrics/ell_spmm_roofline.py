"""Share of its roofline that the Pallas ELL gossip kernel reaches, in
percent: the least time the chip needs for one round's work, counted from
the problem's own shapes (``bench/sparse_counts``: the configuration's
network, payload width d r), over the measured time per call."""
from bench import roofline, sparse_counts, trace

KERNEL = "ell_spmm_pallas"


def read(view):
    if not view.reduced.devices:
        return None
    ns, calls = trace.op_time(view.reduced.devices[0], KERNEL)
    if not calls:
        return None
    flops, nbytes = sparse_counts.config_counts(view.config)
    return roofline.roofline_pct(flops, nbytes, ns / 1e9 / calls,
                                 view.device_kind)
