"""A cell, a traffic mix, a network generator and a per-layer metric that
exist only as new files are found by name and run."""
import json
import time

import jax

from bench import harness, spec
from bench_testcells import ROOT, TINY_CONFIG, write_cell

NEW_READER = '''
def read(view):
    return float(view.solves) if view.reduced.window_ns > 0 else None
'''
NEW_GRAPH = '''
import numpy as np


def adjacency(graph, n):
    return np.ones((n, n)) - np.eye(n)
'''


def test_cell_written_as_new_files_runs(tmp_path):
    root = write_cell(tmp_path, name="newcfg.newmix", metrics=(),
                      config={**TINY_CONFIG, "graph": {"kind": "complete"}})
    (root / "bench" / "metrics" / "solves.traced.py").write_text(NEW_READER)
    (root / "bench" / "graphs" / "complete.py").write_text(NEW_GRAPH)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["per_layer"].append({"name": "solves.traced", "unit": "solves",
                             "moves": "solve_ms",
                             "workloads": ["newcfg.newmix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = spec.load_cell("newcfg.newmix", root)
    assert cell.config["d"] == 24 and cell.traffic["t_outer"] == 30
    assert [m["name"] for m in cell.per_layer] == ["solves.traced"]
    out = harness.run_cell(cell, 9, 0.8, True, jax.devices()[:1],
                           time.perf_counter())
    assert out["correct"]
    assert out["metrics"]["solves.traced"]["value"] >= 1


def test_every_cell_of_the_benchmark_loads():
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    for wl in doc["workloads"]:
        cell = spec.load_cell(wl["name"])
        assert cell.check["subspace_gap_max"] > 0
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                         "solve_ms"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
        assert callable(spec.entry(cell.traffic["entry"]))
        adj = spec.adjacency(cell.config["graph"], cell.config["n_nodes"])
        assert adj.shape == (cell.config["n_nodes"],) * 2
