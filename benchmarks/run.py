"""Benchmark aggregator — one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [module ...]
Prints ``name,us_per_call,derived`` CSV (one row per measurement).
"""
from __future__ import annotations

import importlib
import sys
import time
import traceback

MODULES = [
    "sdot_fused",
    "bdot_fused",
    "sweep_bench",
    "streaming_bench",
    "runtime_bench",
    "serving_bench",
    "table1_eigengap_p2p",
    "table2_connectivity",
    "table3_ring",
    "table4_star",
    "table5_straggler",
    "fig45_baselines",
    "fig6_fdot",
    "table69_realworld",
    "tpu_comm_model",
    "kernel_bench",
    "bdot_blockwise",
    "async_straggler",
]


def main() -> None:
    from repro.launch.device import init_compile_cache

    init_compile_cache()
    args = sys.argv[1:]
    mods = args if args else MODULES
    print("name,us_per_call,derived")
    failures = []
    for name in mods:
        t0 = time.time()
        try:
            mod = importlib.import_module(f".{name}", __package__)
            for row in mod.run():
                print(row.csv(), flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
