"""Share of the gossip rounds run that went through the Pallas ELL kernel,
in percent: 100 * ell_pallas_rounds / rounds_run, over every solve of the
run. On a sparse engine the program counts ``ell_pallas_rounds`` on its
``sdot.solve`` span (all the rounds it runs where the kernel takes the
payload width the solve mixes, else 0); a program that keeps no such
count gives ``None``. ``bench/counters`` reads their totals."""
from bench import counters


def read(view):
    kernel = counters.total("sdot_solve_ell_pallas_rounds_total")
    run = counters.total("sdot_solve_rounds_run_total")
    if kernel is None or not run:
        return None
    return 100.0 * kernel / run
