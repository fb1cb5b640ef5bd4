"""A tiny cell on a large sparse network through the harness: at 300
nodes on a Watts-Strogatz k=6 overlay (2% density) the engine picks sparse
mixing itself, so every gossip round is an ELL round. Sound, it is correct
against the float64 reference; with one ELL slot dropped, it is not; the
calibration's bf16-payload control reads far above its limit."""
import importlib.util
import time

import jax
import pytest

from bench import harness, spec
from bench_testcells import ROOT, write_cell

SPARSE_CONFIG = {"d": 8, "samples": 16 * 300, "n_nodes": 300, "r": 2,
                 "graph": {"kind": "watts_strogatz", "k": 6, "p": 0.1,
                           "seed": 1},
                 "alpha": 2.0}
SPARSE_TRAFFIC = {"entry": "sdot", "operand": "cov",
                  "schedule": {"slope": 0, "offset": 50}, "t_outer": 30}
# Set as the chip cell's limit is (bench/calibrate_ell.py), from CPU
# readings of this cell over seeds 1-6 and 2**32 + 11, 8 solves each: the
# program's largest gap 3.42e-7, the bf16-payload control's least 1.38e-4.
# The limit leaves 5.8x of room above the program and 69x below the
# control; one ELL slot dropped reads orders of magnitude above it.
SPARSE_TINY_LIMIT = 2e-6


@pytest.fixture
def registry(monkeypatch):
    from repro import obs
    from repro.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "_registry", reg)
    return reg


@pytest.fixture
def sparse_root(tmp_path):
    return write_cell(tmp_path, name="sparsetiny.ell", config=SPARSE_CONFIG,
                      traffic=SPARSE_TRAFFIC, limit=SPARSE_TINY_LIMIT,
                      metrics=())


def _run(root, seconds=0.3):
    cell = spec.load_cell("sparsetiny.ell", root)
    return harness.run_cell(cell, 2**32 + 11, seconds, False,
                            jax.devices()[:1], time.perf_counter())


def test_tiny_sparse_cell_runs_sparse_and_is_correct(sparse_root, registry):
    out = _run(sparse_root, seconds=1.0)
    assert out["correct"] and out["failed"] == 0
    gap = out["checks"]["subspace_gap"]
    assert 0 < gap["value"] < gap["limit"] == SPARSE_TINY_LIMIT
    counts = {k: v["value"] for k, v in registry.snapshot().items()}
    # every solve (two warm-ups and the window's) ran on the sparse
    # engine, its 30 x 50 rounds through the CPU's fallback, not the kernel
    solves = counts["sdot_solve_total"]
    assert solves == out["attempted"] + 2
    assert counts["sdot_solve_rounds_run_total"] == 1500 * solves
    assert counts["sdot_solve_ell_pallas_rounds_total"] == 0


def test_tiny_sparse_cell_with_one_ell_slot_dropped_is_not_correct(
        sparse_root, monkeypatch):
    from repro.kernels import ops as kops

    ell_spmm = kops.ell_spmm

    def dropped(idx, val, diag, z, **kw):
        return ell_spmm(idx, val.at[0, 0].set(0.0), diag, z, **kw)

    monkeypatch.setattr(kops, "ell_spmm", dropped)
    jax.clear_caches()
    try:
        out = _run(sparse_root)
    finally:
        jax.clear_caches()
    assert not out["correct"] and out["failed"] > 0
    assert out["checks"]["subspace_gap"]["value"] > 100 * SPARSE_TINY_LIMIT


def test_tiny_sparse_cell_calibration_separates_program_and_control(
        sparse_root):
    path = ROOT / "bench" / "calibrate_ell.py"
    mod_spec = importlib.util.spec_from_file_location("calibrate_ell", path)
    cal = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(cal)
    lines = []
    cal.readings(spec.load_cell("sparsetiny.ell", sparse_root), [2], [2],
                 jax.devices()[:1], out=lines.append)
    summary = lines[-1]
    assert summary["program_correct"] == [True]
    assert summary["control_correct"] == [False]
    assert summary["program_max"] < SPARSE_TINY_LIMIT / 3
    assert summary["control_min"] > 30 * SPARSE_TINY_LIMIT
