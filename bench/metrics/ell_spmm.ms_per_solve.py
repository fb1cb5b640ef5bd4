"""Device time of the Pallas ELL gossip kernel
(``kernels/ell_spmm.ell_spmm_pallas``, one call a sparse gossip round)
per solve, in ms: the summed duration of its custom-call events over the
solves traced."""
from bench import trace

KERNEL = "ell_spmm_pallas"


def read(view):
    if not view.reduced.devices or not view.solves:
        return None
    ns, calls = trace.op_time(view.reduced.devices[0], KERNEL)
    return ns / 1e6 / view.solves if calls else None
