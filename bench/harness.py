"""One run of one cell: set-up, the measured window, the trace reduction
and the comparison with the reference. ``run.py`` wraps it with the chip
check and the printing; tests drive it directly on the CPU.

The timed path is the program's own entry point, which the traffic mix
names (``bench/entries``): ``repro.core.sdot.sdot`` (one chip,
``runtime.run_monolithic`` underneath) or ``repro.core.sdot.sdot_spmd``
(node == chip). The benchmark hands it data,
a ``Graph`` and a schedule that it made itself, and a fresh ``q_init`` per
solve; it does not pass ``q_true``, so the solve traces no error, as users
run it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import pathlib
import shutil
import statistics
import tempfile
import time

import numpy as np

from . import data, reference, spec, trace

__all__ = ["Solver", "Window", "setup", "measure", "compare", "judge",
           "control_solves", "run_cell", "use_compile_cache", "CHECK_SOLVES",
           "TRACE_SECONDS"]

CHECK_SOLVES = 8        # window solves compared with the reference
TRACE_SECONDS = 4.0     # traced part of a --trace 1 window, at most all of it


@dataclasses.dataclass
class Solver:
    d: int
    r: int
    adjacency: np.ndarray
    sched: np.ndarray
    devices: list
    solve: object           # q_init (d, r) -> q_nodes (N, d, r) on device
    operand: dict           # what the program was given, for the reference


def setup(cell: spec.Cell, seed: int, devices) -> Solver:
    """The cell's data, network and engine, from ``--seed`` and the
    configuration, built by the entry the traffic mix names
    (``bench/entries``). Nothing here is timed apart from ``setup_s``."""
    import jax
    from repro.core.topology import Graph

    cfg, tr = cell.config, cell.traffic
    adj = spec.adjacency(cfg["graph"], cfg["n_nodes"], cell.root)
    sched = data.schedule(tr["schedule"], tr["t_outer"])
    solve, operand = spec.entry(tr["entry"], cell.root)(
        cfg, tr, seed, Graph(adj), list(devices), r=cfg["r"],
        t_outer=tr["t_outer"], schedule=sched)
    jax.block_until_ready(operand)
    return Solver(cfg["d"], cfg["r"], adj, sched, list(devices), solve,
                  operand)


@dataclasses.dataclass
class Window:
    latencies: list          # seconds per solve, call to block_until_ready
    seconds: float           # first solve's start to the last one's end
    sample: dict             # solve index -> q_nodes, drawn from the seed
    traced: object = None    # trace.Reduced of the traced part, if any
    counts: dict = None      # compile events inside the window


class _CompileCounter:
    """Counts JAX's trace, compile and cache-load events while it is on."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_hits": "cache_loads"}

    def __init__(self):
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        self.on = False

    def _hit(self, event, *_, **__):
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def __enter__(self):
        from jax import monitoring
        monitoring.register_event_listener(self._hit)
        monitoring.register_event_duration_secs_listener(self._hit)
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False
        from jax import monitoring
        monitoring.unregister_event_listener(self._hit)
        monitoring.unregister_event_duration_listener(self._hit)
        # a cache load also ends in the compile event
        self.counts["compiles"] -= self.counts["cache_loads"]


def measure(solver: Solver, seed: int, seconds: float,
            traced: bool = False) -> Window:
    """Complete solves, back to back, for ``seconds``. With ``traced`` the
    first ``TRACE_SECONDS`` of the window (all of a shorter one) run under
    the profiler and are reduced to a ``trace.Reduced``; the rest runs
    without it.

    A uniform sample of ``CHECK_SOLVES`` solves, drawn from the seed
    (reservoir sampling, since the count is not known in advance), is held
    for the comparison after the window."""
    import jax

    keep, trace_seconds = CHECK_SOLVES, min(TRACE_SECONDS, seconds)
    pick = np.random.default_rng([seed, 2**63])
    sample, lat = {}, []
    reduced, trace_dir = None, None
    span = contextlib.nullcontext
    if traced:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        span = jax.profiler.TraceAnnotation
    k = 0
    with _CompileCounter() as counter:
        w0 = t_end = time.perf_counter()
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
            window_span = span("window")
            window_span.__enter__()
        while True:
            with span("prepare"):
                q0 = data.q_init(seed, k, solver.d, solver.r)
            t0 = time.perf_counter()
            with span("solve_call"):
                q = solver.solve(q0)
            with span("block"):
                q.block_until_ready()
            t_end = time.perf_counter()
            lat.append(t_end - t0)
            if k < keep:
                sample[k] = q
            else:
                j = int(pick.integers(0, k + 1))
                if j < keep:
                    del sample[sorted(sample)[j]]
                    sample[k] = q
            del q
            k += 1
            if trace_dir and t_end - w0 >= trace_seconds:
                window_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                reduced = trace.reduce(trace.load(trace.find_xplane(
                    trace_dir)))
                shutil.rmtree(trace_dir, ignore_errors=True)
                trace_dir, span = None, contextlib.nullcontext
                # the time taken to write and read the trace is no part
                # of the window
                pause = time.perf_counter() - t_end
                w0, t_end = w0 + pause, t_end + pause
            if t_end - w0 >= seconds:
                break
    return Window(lat, t_end - w0, sample, reduced, counter.counts)


def use_compile_cache(root) -> None:
    """JAX's persistent compile cache in ``<root>/.jax_cache``, unless
    ``JAX_COMPILATION_CACHE_DIR`` names another; every program of the cell,
    however quick to compile, is kept, so a run after the first compiles
    nothing."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(pathlib.Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def peak_bytes(devices) -> int | None:
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
             for dev in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def operand_apply(operand: dict, host: bool):
    """How the reference applies the program's input, and that input: on
    the host for the float64 reference, or as it is for the control."""
    if "covs" in operand:
        covs = operand["covs"]
        return reference.cov_apply, np.asarray(covs) if host else covs
    blocks = operand["data"]
    return reference.data_apply, ([np.asarray(x) for x in blocks] if host
                                  else blocks)


def compare(solver: Solver, win: Window, seed: int, limit: float) -> dict:
    """The window's sampled solves against the float64 reference, run from
    the same inputs once the program's device state is freed."""
    idx = sorted(win.sample)
    results = np.stack([np.asarray(win.sample[k], np.float64) for k in idx])
    q0s = np.stack([data.q_init(seed, k, solver.d, solver.r) for k in idx])
    make, host = operand_apply(solver.operand, host=True)
    win.sample.clear()
    solver.operand = solver.solve = None
    gc.collect()
    return judge(results, reference_solves(solver, q0s, make, host), limit)


def judge(results, q_ref, limit: float) -> dict:
    """The verdict on final iterates ``results`` (K, N, d, r): each solve
    fails whose widest subspace gap to the reference's is over ``limit``,
    and all fail if any number is not finite; correct when none fails."""
    gaps = solve_gaps(results, q_ref)
    finite = bool(np.isfinite(results).all())
    failed = (sum(1 for g in gaps if not g <= limit) if finite
              else len(gaps))
    return {"gap": max(gaps) if finite else float("inf"), "gaps": gaps,
            "limit": limit, "checked": len(gaps), "failed": failed,
            "correct": failed == 0 and len(gaps) > 0}


def reference_solves(solver: Solver, q0s, make, host):
    """The float64 reference's final iterates (K, N, d, r) from ``q0s``,
    over the program's input ``host`` copied to the host."""
    ops = reference.Float64
    return reference.iterate(ops, make(ops, host),
                             reference.local_degree_weights(
                                 solver.adjacency), q0s, solver.sched)


def control_solves(solver: Solver, q0s):
    """The control in the program's place: the reference's own arithmetic
    with every product of the apply and the gossip in three bf16 passes
    (``reference.Bf16x3``, ``Precision.HIGH``), over the program's own
    device input, from ``q0s``; (K, N, d, r) on the host."""
    make, op = operand_apply(solver.operand, host=False)
    return reference.iterate(reference.Bf16x3, make(reference.Bf16x3, op),
                             reference.local_degree_weights(
                                 solver.adjacency), q0s, solver.sched)


def solve_gaps(results, q_ref) -> list:
    """Per solve, the widest subspace gap over its nodes."""
    return [reference.subspace_gap(results[i], q_ref[i])
            for i in range(len(q_ref))]


def _end_to_end(win: Window, setup_s: float, peak: int | None) -> dict:
    lat_ms = [x * 1e3 for x in win.latencies]
    out = {"setup_s": (setup_s, "s"),
           "solve_ms": (win.seconds * 1e3 / len(lat_ms), "ms"),
           "solve_p90_ms": (statistics.quantiles(lat_ms, n=10)[-1]
                            if len(lat_ms) > 1 else lat_ms[0], "ms")}
    if peak is not None:
        out["peak_hbm_mb"] = (peak / 1e6, "MB")
    return out


@dataclasses.dataclass
class TraceView:
    """What a per-layer metric reader sees."""
    reduced: trace.Reduced
    config: dict
    traffic: dict
    device_kind: str

    @property
    def solves(self) -> int:
        return self.reduced.solves


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             devices, t_start: float) -> dict:
    """One run of ``cell``; returns the result line's fields plus the
    numbers compared (``checks``) and the window's compile counts."""
    import jax

    solver = setup(cell, seed, devices)
    for k in (-1, -2):      # warm: the first compiles or loads, the second
        jax.block_until_ready(solver.solve(      # must find it all
            data.q_init(seed, k, solver.d, solver.r)))
    setup_s = time.perf_counter() - t_start
    win = measure(solver, seed, seconds, traced)
    peak = peak_bytes(solver.devices)
    dev0 = solver.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    metrics, extra = {}, {}
    if traced:
        red = win.traced
        view = TraceView(red, cell.config, cell.traffic, dev0.device_kind)
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"], cell.root)(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if red.devices:
            device["busy_s"] = (sum(dv.busy_ns for dv in red.devices)
                                / len(red.devices) / 1e9)
        device["window_s"] = red.window_ns / 1e9
        extra["breakdown"] = trace.breakdown(red)
    else:
        e2e = _end_to_end(win, setup_s, peak)
        for m in cell.end_to_end:
            if m["name"] in e2e:
                value, unit = e2e[m["name"]]
                metrics[m["name"]] = {"value": value, "unit": unit}
    result = compare(solver, win, seed, cell.check["subspace_gap_max"])
    return {"correct": result["correct"] and len(win.latencies) > 0, "attempted": len(win.latencies),
            "failed": result["failed"], "metrics": metrics, "device": device,
            **extra, "window": {"solves": len(win.latencies), **win.counts,
                                "longest_s": max(win.latencies),
                                "between_s": win.seconds - sum(win.latencies),
                                "gaps": result["gaps"]},
            "checks": {"subspace_gap": {"value": result["gap"],
                                        "limit": result["limit"]}}}
