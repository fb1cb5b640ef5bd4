"""Readings that set the limit of a cell's check (``bench/checks``).

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--control-seeds 11,12,13]

For each seed it builds the cell as a run does, solves from the first
``harness.CHECK_SOLVES`` initial iterates a run would draw, and gives the
widest subspace gap of those solves to the float64 reference: the
program's reading. For each control seed it also runs the control, the
reference's own arithmetic with every product of the apply and the gossip
in three bf16 passes (``reference.Bf16x3``, what ``Precision.HIGH``
computes), on the same device inputs, and gives its gap: the control's
reading. Each reading is judged as a run's solves are
(``harness.judge``, against the limit in ``bench/checks/<cell>.json``),
and its ``correct`` and ``failed`` are given beside it: the control in the
program's place has to come out not correct.

One JSON line per seed, then a summary line with the largest program
reading, the least control reading and the verdicts. Exits non-zero when
JAX finds no TPU.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import data, harness, spec

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 3
    harness.use_compile_cache(ROOT)
    keep = harness.CHECK_SOLVES
    limit = cell.check["subspace_gap_max"]
    readings = {"program": [], "control": []}
    verdicts = {"program": [], "control": []}
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        t0 = time.perf_counter()
        solver = harness.setup(cell, seed, devices[:cell.chips])
        q0s = np.stack([data.q_init(seed, k, solver.d, solver.r)
                        for k in range(keep)])
        prog = np.stack([np.asarray(solver.solve(q0), np.float64)
                         for q0 in q0s])
        line = {"workload": cell.name, "seed": seed}
        results = {}
        if seed in args.seeds:
            results["program"] = prog
        if seed in args.control_seeds:
            t = time.perf_counter()
            results["control"] = harness.control_solves(solver, q0s)
            line["control_s"] = time.perf_counter() - t
        make, host = harness.operand_apply(solver.operand, host=True)
        solver.operand = solver.solve = None
        t = time.perf_counter()
        q_ref = harness.reference_solves(solver, q0s, make, host)
        for name, res in results.items():
            v = harness.judge(res, q_ref, limit)
            line[name] = v["gap"]
            line[name + "_gaps"] = v["gaps"]
            line[name + "_verdict"] = {"correct": v["correct"],
                                       "failed": v["failed"],
                                       "checked": v["checked"],
                                       "limit": limit}
            readings[name].append(v["gap"])
            verdicts[name].append(v["correct"])
        line["reference_s"] = time.perf_counter() - t
        line["seed_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": cell.name,
                      "program_max": max(readings["program"], default=None),
                      "control_min": min(readings["control"], default=None),
                      "program_seeds": len(readings["program"]),
                      "control_seeds": len(readings["control"]),
                      "limit": limit,
                      "program_correct": verdicts["program"],
                      "control_correct": verdicts["control"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
