"""Finds a cell and everything that belongs to it by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
rest lives in files of their own, so that a later cell or metric is added
as new files and no existing file is edited:

    bench/configs/<config>.json     the deployment (the file BENCHMARK.json
                                    names for it)
    bench/traffic/<traffic>.json    the mix: entry point, operand, schedule
    bench/checks/<cell>.json        the limit that decides ``correct``
    bench/metrics/<metric>.py       the reader of one per-layer metric
    bench/graphs/<kind>.py          a network generator, named by the
                                    configuration's ``graph.kind``
    bench/entries/<entry>.py        the engine and entry point the timed
                                    path calls, named by the mix's ``entry``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

__all__ = ["ROOT", "Cell", "load_cell", "metric_reader", "adjacency",
           "entry"]

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    check: dict
    end_to_end: list
    per_layer: list
    root: pathlib.Path


def _read(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _read(root / "BENCHMARK.json")
    wl = _named(bench["workloads"], name, "workload")
    cfg = _named(bench["configs"], wl["config"], "config")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return Cell(name=name, chips=wl["chips"],
                config=_read(root / cfg["file"]),
                traffic=_read(root / "bench" / "traffic"
                              / f"{wl['traffic']}.json"),
                check=_read(root / "bench" / "checks" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer, root=root)


def _module(kind: str, name: str, root: pathlib.Path):
    """The module in ``bench/<kind>/<name>.py`` under ``root``."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(metric: str, root: pathlib.Path = ROOT):
    """``read(view) -> float | None`` from ``bench/metrics/<metric>.py``."""
    return _module("metrics", metric, root).read


def adjacency(graph: dict, n_nodes: int, root: pathlib.Path = ROOT):
    """The configuration's network, from ``bench/graphs/<kind>.py``."""
    return _module("graphs", graph["kind"], root).adjacency(graph, n_nodes)


def entry(name: str, root: pathlib.Path = ROOT):
    """``build(config, traffic, seed, graph, devices, **kw) -> (solve,
    operand)`` from ``bench/entries/<name>.py``."""
    return _module("entries", name, root).build
