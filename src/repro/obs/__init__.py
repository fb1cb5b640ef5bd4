"""Unified observability layer: profiler spans, span journal, metrics
registry, CLI.

The in-process layer is ``trace_span``: a host span (with integer counts
as its arguments) on the profiler's own clock, so that a ``jax.profiler``
trace holds the program's host phases beside the device's ops. It is what
the hot path uses (``core/sdot``, ``core/runtime``): a handful of spans a
solve, about a microsecond each when no profiler runs; nothing goes to a
file, and their counts add up in the registry below. The journal keeps its
own clock (``time.monotonic``) and never sees these spans.

Three more pieces, strictly OUT-OF-BAND (host-side file appends only —
device math replays bit-identical with tracing on or off):

* ``journal`` — crash-safe append-only JSONL span/event journals, one per
  process attempt, with a torn-tail-tolerant reader;
* ``registry`` — counters / gauges / bucketed histograms with p50/p99 and
  a Prometheus-style exposition;
* ``cli`` (``python -m repro.obs``) — merge per-process journals into one
  timeline, per-phase duration summaries, text exposition, a plain-text
  Gantt, and a ``forensics`` mode reconstructing a dead worker's last
  spans and attributing every injected chaos fault to the phase it fired
  in.

Process wiring: long-lived components (sweep workers, the launcher, the
serving loop) call ``install(workdir, proc)`` once at startup, which opens
an attempt-scoped journal under ``obs_dir_for(workdir)`` (default
``<workdir>/obs``; override with ``REPRO_OBS_DIR``; disable everything
with ``REPRO_OBS=0``) and a fresh process registry. Library seams
(``core/runtime``, ``checkpoint/manager``, chaos hooks) fetch the current
journal via ``get_journal()`` — a no-op shell unless something installed
one, so bare library calls (tests, benchmarks) stay untraced and pay one
attribute check.
"""
from __future__ import annotations

import os
from typing import Optional

from .journal import (ENV_DIR, ENV_OBS, Journal, Span, journal_files,
                      merge_journals, read_journal)
from .registry import Counter, Gauge, Histogram, MetricsRegistry

__all__ = ["Journal", "Span", "read_journal", "merge_journals",
           "journal_files", "Counter", "Gauge", "Histogram",
           "MetricsRegistry", "get_journal", "set_journal", "metrics",
           "install", "obs_dir_for", "trace_span", "ENV_DIR", "ENV_OBS"]

_journal: Journal = Journal.noop()
_registry: MetricsRegistry = MetricsRegistry()


class _TraceSpan:
    """What ``trace_span`` returns; see there."""

    __slots__ = ("_name", "_note")

    def __init__(self, name: str, counts: dict):
        from jax.profiler import TraceAnnotation

        self._name = name
        self._note = TraceAnnotation(name, **counts)
        if counts:
            self._add(counts)

    def __enter__(self) -> "_TraceSpan":
        self._note.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._note.__exit__(*exc)

    def count(self, **counts) -> None:
        """Counts known only at the span's end, recorded as if given when
        it was opened."""
        self._note.set_metadata(**counts)
        self._add(counts)

    def _add(self, counts: dict) -> None:
        stem = self._name.replace(".", "_")
        _registry.counter(f"{stem}_total").inc()
        for key, value in counts.items():
            _registry.counter(f"{stem}_{key}_total").inc(value)


def trace_span(name: str, **counts) -> _TraceSpan:
    """A host span named ``name`` on the profiler's clock: a context
    manager over ``jax.profiler.TraceAnnotation``, so a running
    ``jax.profiler`` trace holds it beside the device's ops, with the
    integer ``counts`` as the span's arguments (its event stats). With no
    profiler running it records nothing in any trace.

    Counts also add up in the process registry (``metrics()``), where an
    in-process reader finds them without a trace: each recording of counts
    (when the span is made, or a ``count(**counts)`` call on the entered
    span for counts known only at its end) adds one to ``<name>_total`` and
    each count to ``<name>_<count>_total`` (dots in ``name`` become ``_``).
    """
    return _TraceSpan(name, counts)


def get_journal() -> Journal:
    """The process journal (a disabled no-op unless ``install``ed)."""
    return _journal


def set_journal(journal: Journal) -> Journal:
    global _journal
    _journal = journal
    return journal


def metrics() -> MetricsRegistry:
    """The process metrics registry (always usable; reset by ``install``)."""
    return _registry


def obs_dir_for(workdir: str) -> Optional[str]:
    """Where a component rooted at ``workdir`` should journal.

    ``REPRO_OBS=0`` -> None (observability fully off); ``REPRO_OBS_DIR``
    overrides; default ``<workdir>/obs`` — tracing is ON by default for
    workdir-rooted components because the journal is out-of-band and its
    cost is a few atomic line appends per chunk boundary."""
    if os.environ.get(ENV_OBS, "").lower() in ("0", "off", "false"):
        return None
    return os.environ.get(ENV_DIR) or os.path.join(workdir, "obs")


def install(workdir: str, proc: str, **static) -> Journal:
    """Open (and make current) an attempt-scoped journal for this process
    plus a FRESH metrics registry wired into it (span durations feed
    ``span_<name>_seconds`` histograms). Returns the journal; a disabled
    no-op journal when observability is off."""
    global _registry
    _registry = MetricsRegistry()
    obs_dir = obs_dir_for(workdir)
    if obs_dir is None:
        return set_journal(Journal.noop())
    return set_journal(Journal.open(obs_dir, proc, registry=_registry,
                                    **static))
