"""Device time of the Pallas gram-apply kernel
(``kernels/gram_update.batched_gram_apply_pallas``) per solve, in ms: the
summed duration of its custom-call events over the solves traced."""
from bench import trace


def read(view):
    if not view.reduced.devices or not view.solves:
        return None
    ns, calls = trace.op_time(view.reduced.devices[0],
                              "batched_gram_apply_pallas")
    return ns / 1e6 / view.solves if calls else None
