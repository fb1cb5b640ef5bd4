"""Calls that missed the program's jit cache (and so traced, then
compiled or loaded from the compile cache), per solve, over every solve
of the run: the ``jit_miss`` counts of the runtime's ``runtime.dispatch``
spans and of ``sdot_spmd.call`` (a fresh ``jax.jit`` on every call), over
the ``sdot.solve`` and ``sdot_spmd.solve`` spans; ``bench/counters``
reads their totals."""
from bench import counters


def read(view):
    misses = counters.total("runtime_dispatch_jit_miss_total",
                            "sdot_spmd_call_jit_miss_total")
    solves = counters.total("sdot_solve_total", "sdot_spmd_solve_total")
    if misses is None or not solves:
        return None
    return misses / solves
