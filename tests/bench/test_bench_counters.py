"""The per-layer metrics read from the program's own counts
(``bench/counters``): each reader's number from known counts, none from a
program that keeps no counts, and a traced run of the tiny cell with and
without them."""
import sys
import time

import pytest

from bench import harness, spec, trace
from bench_testcells import write_cell

COUNTERS = ("gossip.useful_round_share", "runtime.jit_misses_per_solve")


@pytest.fixture
def registry(monkeypatch):
    from repro import obs
    from repro.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    monkeypatch.setattr(obs, "_registry", reg)
    return reg


def _view():
    return harness.TraceView(trace.Reduced(0, 0, []), {}, {}, "cpu")


def _solves(span, calls, **counts):
    from repro.obs import trace_span

    for _ in range(calls):
        with trace_span(span) as s:
            s.count(**counts)


@pytest.mark.parametrize("metric, record, expected", [
    # SA-DOT t+1 capped at 50: 1,324 rounds of the 2,500 the scan runs
    ("gossip.useful_round_share",
     lambda: _solves("sdot.solve", 3, rounds_run=2500, rounds_needed=1324),
     100 * 1324 / 2500),
    ("gossip.useful_round_share",
     lambda: _solves("sdot_spmd.solve", 2, rounds_run=5000,
                     rounds_needed=5000), 100.0),
    # one compile in four solves through the runtime
    ("runtime.jit_misses_per_solve",
     lambda: (_solves("sdot.solve", 4, rounds_run=1, rounds_needed=1),
              _solves("runtime.dispatch", 1, jit_miss=1),
              _solves("runtime.dispatch", 3, jit_miss=0)), 0.25),
    # sdot_spmd's fresh jit on every call
    ("runtime.jit_misses_per_solve",
     lambda: (_solves("sdot_spmd.solve", 5, rounds_run=1, rounds_needed=1),
              _solves("sdot_spmd.call", 5, jit_miss=1)), 1.0),
], ids=["sadot", "spmd-const", "runtime", "spmd"])
def test_counter_reader_reads_the_programs_counts(registry, metric, record,
                                                  expected):
    record()
    assert spec.metric_reader(metric)(_view()) == pytest.approx(expected)


@pytest.mark.parametrize("metric", COUNTERS)
@pytest.mark.parametrize("program", ["no counts", "no registry"])
def test_counter_reader_gives_none_without_counts(registry, monkeypatch,
                                                  metric, program):
    if program == "no registry":
        monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert spec.metric_reader(metric)(_view()) is None


class _NoSpan:
    """A program without spans or counts: the parent's shape."""

    def __init__(self, *_, **__):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass

    def count(self, **_):
        pass


@pytest.mark.parametrize("program", ["spans", "no spans"])
def test_traced_tiny_cell_with_and_without_the_programs_counts(
        tmp_path, registry, monkeypatch, program):
    import jax
    from repro.core import runtime, sdot

    if program == "no spans":
        monkeypatch.setattr(sdot, "trace_span", _NoSpan)
        monkeypatch.setattr(runtime, "trace_span", _NoSpan)
    jax.clear_caches()              # so the run's first solve compiles
    metrics = ("device.idle_share",) + COUNTERS
    root = write_cell(tmp_path, metrics=metrics)
    cell = spec.load_cell("tiny.mix", root)
    out = harness.run_cell(cell, 2**33 + 5, 0.3, True, jax.devices()[:1],
                           time.perf_counter())
    assert out["correct"] is True
    got = out["metrics"]
    if program == "no spans":
        assert got == {}            # no device plane on the CPU either
    else:
        assert got["gossip.useful_round_share"]["value"] == 100.0
        # one compile over the two warm-up solves and the window's
        solves = out["attempted"] + 2
        assert got["runtime.jit_misses_per_solve"]["value"] == (
            pytest.approx(1 / solves))
        assert set(got) == set(COUNTERS)
