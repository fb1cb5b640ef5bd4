"""Reduction from a profiler trace to per-device busy time, op time and
idle gaps, each gap labelled by what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
``Plane``/``Line`` tuples; ``reduce`` needs nothing else, so a test can
hand it a small trace written by hand.

On a TPU the device plane ``/device:TPU:<i>`` has a line ``XLA Ops`` whose
events are HLO ops named by their HLO text; ops nest (a ``while`` spans
its body's ops), so an op's time here is its self time.

The window is the host span named ``window`` that the harness opens around
the traced solves. A device's busy time is the union of its op intervals
inside that window; idle is the rest. Each idle gap takes the name of the
harness span (``prepare``, ``solve_call``, ``block``) in which it falls.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import re

__all__ = ["Line", "Plane", "Device", "Reduced", "load", "find_xplane",
           "reduce", "breakdown", "short_name", "op_time"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
WINDOW = "window"
SPANS = ("prepare", "solve_call", "block")


@dataclasses.dataclass
class Line:
    name: str
    events: list          # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


@dataclasses.dataclass
class Device:
    name: str
    busy_ns: float
    op_ns: collections.Counter       # op name -> self time
    op_n: collections.Counter        # op name -> events
    idle_ns: collections.Counter     # host span name -> idle time in it


@dataclasses.dataclass
class Reduced:
    window_ns: float
    solves: int
    devices: list


def find_xplane(trace_dir) -> str:
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return found[0]


def load(path) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    return [Plane(p.name, [Line(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                          for e in ln.events])
                           for ln in p.lines])
            for p in data.planes]


def short_name(hlo: str) -> str:
    """``%fusion.43 = f32[..] fusion(..)`` -> ``fusion.43``: the op's name
    in the trace is its HLO text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def op_time(device: "Device", name: str):
    """Time and event count of the ops called ``name``, whatever number
    XLA appended (``name``, ``name.7``)."""
    keys = [k for k in device.op_ns if k == name or (
        k.startswith(name + ".") and k[len(name) + 1:].isdigit())]
    return (sum(device.op_ns[k] for k in keys),
            sum(device.op_n[k] for k in keys))


def _self_times(ops):
    """Per op name, its events' time less the time of the ops nested in
    them (a ``while`` contains its body's ops), and its event count."""
    self_ns, count = collections.Counter(), collections.Counter()
    stack = []                      # (end, name) of the enclosing ops
    for s, e, name in ops:          # sorted by start
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1][1]] -= min(e, stack[-1][0]) - s
        self_ns[name] += e - s
        count[name] += 1
        stack.append((e, name))
    return self_ns, count


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_spans(planes):
    spans = [(s, s + d, name) for p in planes if not DEVICE_PLANE.match(p.name)
             for ln in p.lines for name, s, d in ln.events
             if name in SPANS or name == WINDOW]
    windows = [(s, e) for s, e, name in spans if name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one '{WINDOW}' span, found "
                           f"{len(windows)}")
    return windows[0], sorted(x for x in spans if x[2] != WINDOW)


def reduce(planes) -> Reduced:
    (w0, w1), spans = _host_spans(planes)
    starts = [s for s, _, _ in spans]

    def label(t):
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and spans[i][0] <= t < spans[i][1]:
            return spans[i][2]
        return "none"

    devices = []
    for p in planes:
        if not DEVICE_PLANE.match(p.name):
            continue
        ops = [(max(s, w0), min(s + d, w1), short_name(name))
                     for ln in p.lines if ln.name == OP_LINE
                     for name, s, d in ln.events
                     if min(s + d, w1) > max(s, w0)]
        ops.sort(key=lambda op: (op[0], -op[1]))    # an op before its body
        op_ns, op_n = _self_times(ops)
        busy = _union((s, e) for s, e, _ in ops)
        idle = collections.Counter()
        edges = [w0] + [t for iv in busy for t in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle[label((a + b) / 2)] += b - a
        devices.append(Device(p.name, sum(e - s for s, e in busy), op_ns,
                              op_n, idle))
    devices.sort(key=lambda dv: int(DEVICE_PLANE.match(dv.name).group(1)))
    solves = sum(1 for s, e, name in spans
                 if name == "solve_call" and w0 <= s and e <= w1)
    return Reduced(w1 - w0, solves, devices)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """Device ops that took most time and idle time by host span, both in
    seconds averaged over the devices."""
    n = max(len(red.devices), 1)
    ops, idle = collections.Counter(), collections.Counter()
    for dv in red.devices:
        ops.update(dv.op_ns)
        idle.update(dv.idle_ns)
    return {"device_ops": [[k, v / n / 1e9] for k, v in ops.most_common(top)],
            "idle_gaps": [[k, v / n / 1e9] for k, v in idle.most_common(top)]}
