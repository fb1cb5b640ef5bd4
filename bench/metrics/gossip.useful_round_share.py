"""Share of the gossip rounds run that the schedule asks for, in percent:
100 * rounds needed / rounds run, over every solve of the run. The
program counts both as a solve starts (``rounds_needed``, the sum of the
schedule, and ``rounds_run``, t_outer * t_max, since the masked scan runs
t_max rounds in every outer iteration) on its ``sdot.solve`` or
``sdot_spmd.solve`` span; ``bench/counters`` reads their totals."""
from bench import counters

SPANS = ("sdot_solve", "sdot_spmd_solve")


def read(view):
    run = counters.total(*(f"{s}_rounds_run_total" for s in SPANS))
    needed = counters.total(*(f"{s}_rounds_needed_total" for s in SPANS))
    if not run or needed is None:
        return None
    return 100.0 * needed / run
