"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations and metrics are named in ``BENCHMARK.json`` at the
root of the checkout. The run builds the cell's data from ``--seed``, warms
its one solve program (from JAX's compile cache in ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another), solves back to back
for ``--seconds``, and compares a sample of the window's solves with a
float64 reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last the numbers compared beside their
limits (``checks``), which also end standard error.

It exits non-zero and prints no result when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

EXIT_NO_CHIP = 3


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _args(argv)
    from bench import harness, spec

    cell = spec.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return EXIT_NO_CHIP
    harness.use_compile_cache(ROOT)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           devices[:cell.chips], T_START)
    win = out.pop("window")
    print(f"bench: {win['solves']} solves, the longest {win['longest_s']!r}"
          f" s, {win['between_s']!r} s between them; inside the window "
          f"{win['traces']} traces, {win['compiles']} compiles, "
          f"{win['cache_loads']} compile-cache loads; sampled gaps "
          f"{win['gaps']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
