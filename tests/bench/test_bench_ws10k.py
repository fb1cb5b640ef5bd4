"""The 10,000-site sparse cell ``ws10k.sdot_ell`` and what it adds to the
benchmark: the Watts-Strogatz copy against the program's generator, the
ELL kernel's counts by hand, the three readers on a hand-made trace and
registry (and ``None`` without them), the cell as ``BENCHMARK.json``
gives it, the command's refusal without a TPU, and the calibration's
bf16-payload control on a tiny sparse cell."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, sparse_counts, spec, trace
from bench_testcells import ROOT

CELL = "ws10k.sdot_ell"
WS10K = {"n_nodes": 10_000, "d": 32, "r": 4,
         "graph": {"kind": "watts_strogatz", "k": 6, "p": 0.1, "seed": 1}}
READERS = ("ell_spmm.ms_per_solve", "ell_spmm_roofline",
           "gossip.ell_kernel_round_share")


@pytest.fixture
def registry(monkeypatch):
    from repro import obs
    from repro.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "_registry", reg)
    return reg


@pytest.mark.parametrize("n, seed", [(300, 1), (64, 2**32 + 5)])
def test_ws_copy_matches_the_program_and_keeps_n_k_over_2_edges(n, seed):
    from repro.core.topology import watts_strogatz

    graph = {"kind": "watts_strogatz", "k": 6, "p": 0.1, "seed": seed}
    adj = spec.adjacency(graph, n)
    np.testing.assert_array_equal(
        adj, watts_strogatz(n, k=6, p=0.1, seed=seed).adjacency)
    assert adj.sum() == n * 6          # n k / 2 edges, both directions
    assert (adj == adj.T).all() and not adj.diagonal().any()


def test_ws_copy_refuses_an_odd_k():
    with pytest.raises(ValueError):
        spec.adjacency({"kind": "watts_strogatz", "k": 5, "p": 0.1,
                        "seed": 1}, 50)


def test_ell_counts_by_hand_at_ws10k_shapes():
    # K = d r = 128 columns; 60,000 off-diagonal entries (N k)
    flops, nbytes = sparse_counts.ell_spmm_counts(10_000, 60_000, 128)
    assert flops == 2 * (60_000 + 10_000) * 128 == 17_920_000
    # payload read and written (f32), weight + index per entry, diagonal
    assert nbytes == 5_120_000 * 2 + 480_000 + 40_000 == 10_760_000


def test_config_counts_take_nnz_from_the_network():
    small = {**WS10K, "n_nodes": 300}
    assert sparse_counts.config_counts(small) == \
        sparse_counts.ell_spmm_counts(300, 1_800, 128)
    er = {"n_nodes": 40, "d": 8, "r": 2,
          "graph": {"kind": "erdos_renyi", "p": 0.2, "seed": 3}}
    nnz = int(spec.adjacency(er["graph"], 40).sum())
    assert sparse_counts.config_counts(er) == \
        sparse_counts.ell_spmm_counts(40, nnz, 16)


def _view(per_call_ns=0.0, calls=0, solves=2, config=None):
    op_ns, op_n = trace.collections.Counter(), trace.collections.Counter()
    if calls:
        op_ns["ell_spmm_pallas.6"] = calls * per_call_ns
        op_n["ell_spmm_pallas.6"] = calls
    op_ns["fusion.3"], op_n["fusion.3"] = 5e6, 10
    dv = trace.Device("/device:TPU:0", 0, op_ns, op_n, None)
    return harness.TraceView(trace.Reduced(1e9, solves, [dv]),
                             config or WS10K, {}, "TPU v5 lite")


def _ws10k_counts(monkeypatch):
    # the 10,000-node draw is the chip host's work: the same counts from
    # the WS edge count, N k off-diagonal entries
    monkeypatch.setattr(sparse_counts, "_nnz", lambda graph, n: n * 6)


def test_kernel_readers_on_a_hand_made_trace(monkeypatch):
    _ws10k_counts(monkeypatch)
    flops, nbytes = sparse_counts.ell_spmm_counts(10_000, 60_000, 128)
    per_call_ns = 25 * nbytes / 819e9 * 1e9            # 4% of the roofline
    view = _view(per_call_ns, calls=3_000, solves=2)   # 1,500 rounds a solve
    assert spec.metric_reader("ell_spmm_roofline")(view) == \
        pytest.approx(4.0)
    assert spec.metric_reader("ell_spmm.ms_per_solve")(view) == \
        pytest.approx(1_500 * per_call_ns / 1e6)


@pytest.mark.parametrize("metric", READERS[:2])
@pytest.mark.parametrize("trace_has", ["no kernel", "no device"])
def test_kernel_readers_give_none_without_the_kernel(metric, trace_has):
    view = _view()
    if trace_has == "no device":
        view = harness.TraceView(trace.Reduced(1e9, 2, []), WS10K, {},
                                 "TPU v5 lite")
    assert spec.metric_reader(metric)(view) is None


def _solves(calls, **counts):
    from repro.obs import trace_span

    for _ in range(calls):
        with trace_span("sdot.solve") as s:
            s.count(**counts)


@pytest.mark.parametrize("pallas, expected", [(1500, 100.0), (0, 0.0)])
def test_round_share_reads_the_programs_counts(registry, pallas, expected):
    _solves(3, rounds_run=1500, rounds_needed=1500, ell_pallas_rounds=pallas)
    assert spec.metric_reader("gossip.ell_kernel_round_share")(_view()) == \
        pytest.approx(expected)


@pytest.mark.parametrize("program", ["dense counts only", "no registry"])
def test_round_share_gives_none_without_the_count(registry, monkeypatch,
                                                  program):
    _solves(2, rounds_run=2500, rounds_needed=2500)
    if program == "no registry":
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert spec.metric_reader("gossip.ell_kernel_round_share")(_view()) \
        is None


def test_cell_as_the_benchmark_gives_it():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1
    assert {k: cell.config[k] for k in WS10K} == WS10K
    assert cell.config["samples"] == 64 * 10_000
    assert cell.traffic["t_outer"] == 30
    assert cell.traffic["schedule"] == {"slope": 0, "offset": 50}
    assert {m["name"] for m in cell.end_to_end} == {
        "solve_ms", "solve_p90_ms", "peak_hbm_mb", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        *READERS, "device.idle_share", "gossip.useful_round_share",
        "runtime.jit_misses_per_solve"}
    for m in cell.per_layer:
        spec.metric_reader(m["name"])      # every reader is there


def test_cell_is_past_the_sparse_engines_threshold():
    from repro.core.sparse import AUTO_MAX_DENSITY, AUTO_MIN_NODES

    n, k = WS10K["n_nodes"], WS10K["graph"]["k"]
    assert n >= AUTO_MIN_NODES and k / n <= AUTO_MAX_DENSITY


def test_command_without_a_tpu_exits_nonzero_with_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def _calibrate_ell():
    path = ROOT / "bench" / "calibrate_ell.py"
    mod_spec = importlib.util.spec_from_file_location("calibrate_ell", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def test_payload_control_rounds_only_the_payload():
    import jax.numpy as jnp

    ops = _calibrate_ell().payload_control()
    rng = np.random.default_rng(0)
    w, z = (rng.random((64, 64)).astype(np.float32),
            rng.standard_normal((64, 16)).astype(np.float32))
    got = np.asarray(ops.dot(ops.prep(w), z), np.float64)
    z16 = np.asarray(jnp.asarray(z).astype(jnp.bfloat16), np.float64)
    np.testing.assert_allclose(got, w.astype(np.float64) @ z16, rtol=1e-5,
                               atol=1e-5)
    assert np.abs(got - w.astype(np.float64) @ z).max() > 1e-4


def test_calibrate_ell_without_a_tpu_exits_nonzero():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/calibrate_ell.py", "--workload", CELL,
         "--seeds", "1", "--control-seeds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and not proc.stdout.strip()
