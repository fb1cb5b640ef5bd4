"""The program's own measurement: host spans on the profiler's clock with
their counts, the counts in the process registry, and the step scopes in
the compiled program's op metadata."""
import glob
import json
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import runtime
from repro.core import sdot as sdot_mod
from repro.core.async_gossip import AsyncConsensus
from repro.core.consensus import DenseConsensus
from repro.core.netfaults import FaultyConsensus, NetFaultModel
from repro.core.sparse import SparseW
from repro.core.topology import erdos_renyi
from repro.obs.registry import MetricsRegistry

ROOT = pathlib.Path(__file__).resolve().parents[1]
N, D, R = 5, 12, 2
SDOT_SPANS = {"sdot.solve", "sdot.program", "sdot.prepare", "runtime.init",
              "runtime.dispatch", "runtime.sync", "runtime.finalize"}
SPMD_SPANS = {"sdot_spmd.solve", "sdot_spmd.prepare", "sdot_spmd.call"}
SCOPES = ("sdot.apply", "sdot.gossip", "sdot.debias", "sdot.qr")


@pytest.fixture
def registry(monkeypatch):
    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "_registry", reg)
    return reg


def _covs(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack([np.cov(rng.normal(size=(D, 40)))
                                 for _ in range(N)]), jnp.float32)


def _host_spans(trace_dir):
    """(name, stats) of every host event in the trace under
    ``trace_dir`` whose name is a program span."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if not p.name.startswith("/device:")
            for ln in p.lines for e in ln.events
            if e.name.startswith(("sdot.", "sdot_spmd.", "runtime."))]


def test_span_counts_go_to_trace_and_registry(tmp_path, registry):
    jax.profiler.start_trace(str(tmp_path))
    with obs.trace_span("a.b", hits=2):
        pass
    with obs.trace_span("a.c") as span:
        span.count(hits=3)
    with obs.trace_span("a.d"):
        pass
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    stats = {e.name: dict(e.stats)
             for p in ProfileData.from_file(path).planes
             for ln in p.lines for e in ln.events if e.name.startswith("a.")}
    assert stats == {"a.b": {"hits": 2}, "a.c": {"hits": 3}, "a.d": {}}
    assert {k: v["value"] for k, v in registry.snapshot().items()} == {
        "a_b_total": 1, "a_b_hits_total": 2,
        "a_c_total": 1, "a_c_hits_total": 3}


def test_traced_sdot_writes_its_spans_and_counts(tmp_path, registry):
    eng = DenseConsensus(erdos_renyi(N, 0.6, seed=1))
    sched = np.array([1, 2, 3, 4, 4, 4])
    kw = dict(covs=_covs(), engine=eng, r=R, t_outer=6, schedule=sched)
    sdot_mod.sdot(**kw).q_nodes.block_until_ready()        # compiles
    jax.profiler.start_trace(str(tmp_path))
    sdot_mod.sdot(**kw).q_nodes.block_until_ready()
    jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    assert {name for name, _ in spans} == SDOT_SPANS
    stats = dict(spans)
    assert stats["sdot.solve"] == {"rounds_run": 24, "rounds_needed": 18}
    assert stats["runtime.dispatch"] == {"jit_miss": 0}
    counts = {k: v["value"] for k, v in registry.snapshot().items()}
    assert counts == {"sdot_solve_total": 2,
                      "sdot_solve_rounds_run_total": 48,
                      "sdot_solve_rounds_needed_total": 36,
                      "runtime_dispatch_total": 2,
                      "runtime_dispatch_jit_miss_total": 1}


@pytest.mark.parametrize("kernel", [False, True], ids=["cpu", "kernel"])
def test_sdot_prepare_counts_the_stack_it_builds(registry, monkeypatch,
                                                 kernel):
    """On the data operand ``sdot.prepare`` counts the stack's columns and
    whether they are the gram kernel's tiled width: n_max and 0 where the
    apply takes the oracle, 768 and 1 for 662 samples on the kernel's path
    (the program is built, not run, since the kernel needs the chip)."""
    from repro.kernels import ops

    if kernel:
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    rng = np.random.default_rng(0)
    data = [jnp.asarray(rng.normal(size=(D, 662 - i % 2)), jnp.float32)
            for i in range(N)]
    sdot_mod.sdot_program(data=data, engine=DenseConsensus(
        erdos_renyi(N, 0.6, seed=1)), r=R, t_outer=2)
    counts = {k: v["value"] for k, v in registry.snapshot().items()}
    assert counts == {"sdot_prepare_total": 1,
                      "sdot_prepare_stack_cols_total": 768 if kernel else 662,
                      "sdot_prepare_stack_tiled_total": int(kernel)}


@pytest.mark.parametrize("engine, kernel, pallas_rounds", [
    ("dense", None, None),
    # on the CPU the ELL round takes a fallback, so no round is the kernel's
    ("sparse", None, 0),
    ("sparse", "pallas", 24),
    ("faulty-sparse", "pallas", 24),
], ids=["dense", "sparse-fallback", "sparse-kernel", "faulty-sparse-kernel"])
def test_sdot_solve_counts_ell_rounds_on_a_sparse_engine(
        registry, monkeypatch, engine, kernel, pallas_rounds):
    g = erdos_renyi(N, 0.6, seed=1)
    eng = (FaultyConsensus(graph=g, faults=NetFaultModel(p_drop=0.2),
                           seed=0, sparse=True)
           if engine == "faulty-sparse"
           else DenseConsensus(g, sparse=engine == "sparse"))
    if kernel:
        widths = []

        def path(self, k):
            widths.append(k)
            return kernel
        monkeypatch.setattr(SparseW, "kernel_path", path)
    sdot_mod.sdot(covs=_covs(), engine=eng, r=R, t_outer=6,
                  schedule=np.array([1, 2, 3, 4, 4, 4])
                  ).q_nodes.block_until_ready()
    counts = {k: v["value"] for k, v in registry.snapshot().items()
              if k.startswith("sdot_solve")}
    want = {"sdot_solve_total": 1, "sdot_solve_rounds_run_total": 24,
            "sdot_solve_rounds_needed_total": 18}
    if pallas_rounds is not None:
        want["sdot_solve_ell_pallas_rounds_total"] = pallas_rounds
    assert counts == want
    if kernel:
        assert widths == [D * R]      # the payload the solve mixes


def test_kernel_path_is_counted_at_the_width_mixed(monkeypatch):
    """At N=10,000 and ELL width 12 on a TPU, the path of a d r = 128
    payload is the kernel's; a payload whose double-buffered VMEM passes
    the kernel's guard takes a fallback, which a count at width 1 would
    have called the kernel's."""
    from repro.kernels import ops as kops

    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    n, width = 10_000, 12
    sw = SparseW(jnp.zeros((n, width), jnp.int32), jnp.zeros((n, width)),
                 jnp.ones((n,)), jnp.zeros((n,), jnp.int32), n, width)
    assert sw.kernel_path(128) == "pallas"        # 10.8 MB of VMEM
    with pytest.warns(UserWarning, match="guard"):
        wide = sw.kernel_path(1024)               # 82.9 MB > 40 MiB
    assert wide.startswith("fallback_")
    assert sw.kernel_path(1) == "pallas"


def _faulty():
    return FaultyConsensus(graph=erdos_renyi(N, 0.6, seed=1),
                           faults=NetFaultModel(p_drop=0.2), seed=0)


@pytest.mark.parametrize("engine, operand, scopes", [
    (lambda: DenseConsensus(erdos_renyi(N, 0.6, seed=1)), "covs", SCOPES),
    (lambda: DenseConsensus(erdos_renyi(N, 0.6, seed=1)), "data", SCOPES),
    (lambda: AsyncConsensus(erdos_renyi(N, 0.6, seed=1), p_awake=0.7,
                            seed=0), "covs",
     ("sdot.apply", "sdot.gossip", "sdot.qr")),
    (_faulty, "covs", SCOPES),
], ids=["dense-cov", "dense-data", "async", "faulty"])
def test_compiled_program_names_each_step(engine, operand, scopes):
    if operand == "covs":
        op = {"covs": _covs()}
    else:
        rng = np.random.default_rng(1)
        op = {"data": [jnp.asarray(rng.normal(size=(D, 20 + i)), jnp.float32)
                       for i in range(N)]}
    prog = sdot_mod.sdot_program(**op, engine=engine(), r=R, t_outer=3,
                                 t_c=4)
    text = runtime.lower_monolithic(prog).compile().as_text()
    names = set(re.findall(r'op_name="[^"]*?(sdot\.[a-z]+)', text))
    assert set(scopes) <= names


def test_compiled_sparse_program_names_the_ell_round():
    """On a sparse engine every gossip round's ops sit under
    ``gossip.ell_spmm``, inside ``sdot.gossip``."""
    from repro.core.topology import watts_strogatz

    eng = DenseConsensus(watts_strogatz(300, k=6, p=0.1, seed=1))
    assert eng.is_sparse
    covs = jnp.broadcast_to(_covs()[0], (300, D, D))
    prog = sdot_mod.sdot_program(covs=covs, engine=eng, r=R, t_outer=3,
                                 t_c=4)
    text = runtime.lower_monolithic(prog).compile().as_text()
    paths = re.findall(r'op_name="([^"]*gossip\.ell_spmm[^"]*)"', text)
    assert paths and all("sdot.gossip/" in p for p in paths)


SPMD_CHILD = r'''
import glob, json, sys, tempfile
sys.path.insert(0, sys.argv[1] + "/src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from jax.profiler import ProfileData
from repro import obs
from repro.core import sdot as sdot_mod
from repro.core.consensus import SpmdConsensus
from repro.core.topology import ring

n, d, r = 4, 8, 2
rng = np.random.default_rng(0)
covs = jnp.asarray(np.stack([np.cov(rng.normal(size=(d, 30)))
                             for _ in range(n)]), jnp.float32)
eng = SpmdConsensus(Mesh(np.array(jax.devices()[:n]), ("node",)), "node",
                    graph=ring(n))
kw = dict(engine=eng, r=r, t_outer=4, schedule=np.array([1, 2, 3, 3]))
sdot_mod.sdot_spmd(covs=covs, **kw).q_nodes.block_until_ready()
program, = eng._spmd_programs.values()   # the one the call above built
S = jax.ShapeDtypeStruct
text = program.lower(covs, S((n, d, r), jnp.float32), S((4,), jnp.int32),
                     eng.debias_table(3), S((d, r), jnp.float32)
                     ).as_text(debug_info=True)
out = tempfile.mkdtemp()
jax.profiler.start_trace(out)
sdot_mod.sdot_spmd(covs=covs, **kw).q_nodes.block_until_ready()
jax.profiler.stop_trace()
path, = glob.glob(out + "/**/*.xplane.pb", recursive=True)
spans = {e.name: dict(e.stats) for p in ProfileData.from_file(path).planes
         for ln in p.lines for e in ln.events
         if e.name.startswith("sdot_spmd.")}
print(json.dumps({"spans": spans,
                  "scopes": sorted(s for s in ("sdot.apply", "sdot.gossip",
                                               "sdot.debias", "sdot.qr")
                                   if s in text),
                  "counts": {k: v["value"] for k, v in
                             obs.metrics().snapshot().items()}}))
'''


def test_traced_sdot_spmd_writes_its_spans_and_scopes():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run([sys.executable, "-c", SPMD_CHILD, str(ROOT)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(out["spans"]) == SPMD_SPANS
    assert out["spans"]["sdot_spmd.solve"] == {"rounds_run": 12,
                                               "rounds_needed": 9}
    # the traced call reuses the program the first call built
    assert out["spans"]["sdot_spmd.call"] == {"jit_miss": 0}
    assert out["scopes"] == sorted(SCOPES)
    # the first call ran sdot_spmd once more, outside the trace
    assert out["counts"] == {"sdot_spmd_solve_total": 2,
                             "sdot_spmd_solve_rounds_run_total": 24,
                             "sdot_spmd_solve_rounds_needed_total": 18,
                             "sdot_spmd_call_total": 2,
                             "sdot_spmd_call_jit_miss_total": 1}
