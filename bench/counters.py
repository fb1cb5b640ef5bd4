"""The program's own counts, for the per-layer metrics whose source is
``program_counter``.

The program adds up the counts of its host spans in its process's metrics
registry (``repro.obs.trace_span``: ``<span>_total`` for the spans that
carry counts, ``<span>_<count>_total`` for each count, dots made ``_``).
The timed path runs in this process, so a reader finds them there after
the window: totals over every solve of the run, warm-up included. A
program that keeps no such count, or has no registry, gives ``None``;
nothing here raises.
"""
from __future__ import annotations

__all__ = ["total"]


def total(*names: str) -> float | None:
    """The sum of the program's counters ``names``, or ``None`` when it
    keeps none of them."""
    try:
        from repro.obs import metrics
    except ImportError:
        return None
    snap = metrics().snapshot()
    values = [snap[n]["value"] for n in names
              if snap.get(n, {}).get("type") == "counter"]
    return float(sum(values)) if values else None
