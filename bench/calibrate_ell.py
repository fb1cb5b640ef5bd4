"""Readings that set the limit of a cell whose gossip runs on the sparse
ELL path (``bench/checks``), with the control that path has.

    python3 bench/calibrate_ell.py --workload <cell> --seeds 11,12,... \\
        [--control-seeds 11,12,13]

The program's reading is ``calibrate.py``'s: for each seed, the widest
subspace gap to the float64 reference of the first
``harness.CHECK_SOLVES`` solves a run would draw. The control is the
reference computed in the nearest precision below the configuration's:
the cov apply in three bf16 passes (``reference.Bf16x3``, what
``Precision.HIGH`` computes, one step below the MXU's ``HIGHEST``) and
the gossip payload rounded to bf16 before each mix, with f32 weights and
sums, as the program's own bf16-payload ELL engine (``SparseW``'s
``payload_dtype``) rounds it. The ELL round is f32 work on the VPU, which
has no three-pass step between f32 and bf16, so ``calibrate.py``'s
control, three-pass products for the gossip too, reads only a little
above the program on such a cell.

The schedule must be constant: W^{T_c} is then formed once and shared by
every seed (each mix is one product with it, as ``reference.iterate``
takes it). One JSON line per seed, then a summary line, as
``calibrate.py`` gives them. Exits non-zero when JAX finds no TPU.
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


def payload_control():
    """``reference.iterate``'s ops for the gossip: its operand (W^t) kept
    in f32, the payload rounded to bf16, the product summed in f32; the
    QR and the host copy as ``reference.Bf16x3``'s."""
    import jax.numpy as jnp

    from bench import reference

    class Bf16Payload(reference.Bf16x3):
        @staticmethod
        def prep(a):
            return jnp.asarray(a, jnp.float32)

        @staticmethod
        def dot(a, b):
            return reference._mm(a, reference._bf16(b))

    return Bf16Payload


def readings(cell, seeds, control_seeds, devices, out=print):
    """The per-seed lines and the summary, each handed to ``out``."""
    import numpy as np

    from bench import data, harness, reference

    keep = harness.CHECK_SOLVES
    limit = cell.check["subspace_gap_max"]
    sched = data.schedule(cell.traffic["schedule"], cell.traffic["t_outer"])
    if len(set(int(t) for t in sched)) != 1:
        raise ValueError("calibrate_ell needs a constant schedule")
    w_t, ops_c = None, payload_control()
    ones = np.ones(len(sched), dtype=int)     # one product with W^{T_c}
    found = {"program": [], "control": []}
    verdicts = {"program": [], "control": []}
    for seed in sorted(set(seeds) | set(control_seeds)):
        t0 = time.perf_counter()
        solver = harness.setup(cell, seed, devices)
        if w_t is None:
            t = time.perf_counter()
            w_t = np.linalg.matrix_power(
                reference.local_degree_weights(solver.adjacency),
                int(sched[0]))
            out({"w_power_s": time.perf_counter() - t})
        q0s = np.stack([data.q_init(seed, k, solver.d, solver.r)
                        for k in range(keep)])
        results = {}
        if seed in seeds:
            results["program"] = np.stack(
                [np.asarray(solver.solve(q0), np.float64) for q0 in q0s])
        line = {"workload": cell.name, "seed": seed}
        if seed in control_seeds:
            t = time.perf_counter()
            apply, op = harness.operand_apply(solver.operand, host=False)
            results["control"] = reference.iterate(
                ops_c, apply(reference.Bf16x3, op), w_t, q0s, ones)
            line["control_s"] = time.perf_counter() - t
        make, host = harness.operand_apply(solver.operand, host=True)
        solver.operand = solver.solve = None
        t = time.perf_counter()
        q_ref = reference.iterate(reference.Float64,
                                  make(reference.Float64, host), w_t, q0s,
                                  ones)
        line["reference_s"] = time.perf_counter() - t
        for name, res in results.items():
            v = harness.judge(res, q_ref, limit)
            line[name] = v["gap"]
            line[name + "_gaps"] = v["gaps"]
            line[name + "_verdict"] = {"correct": v["correct"],
                                       "failed": v["failed"],
                                       "checked": v["checked"],
                                       "limit": limit}
            found[name].append(v["gap"])
            verdicts[name].append(v["correct"])
        line["seed_s"] = time.perf_counter() - t0
        out(line)
    out({"workload": cell.name,
         "program_max": max(found["program"], default=None),
         "control_min": min(found["control"], default=None),
         "program_seeds": len(found["program"]),
         "control_seeds": len(found["control"]), "limit": limit,
         "program_correct": verdicts["program"],
         "control_correct": verdicts["control"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)

    import jax

    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("calibrate_ell: needs the cell's TPU chips", file=sys.stderr)
        return 3
    harness.use_compile_cache(ROOT)
    readings(cell, args.seeds, args.control_seeds, devices[:cell.chips],
             out=lambda line: print(json.dumps(line), flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
