"""The engine's own spans in a traced window.

The program records its spans (``repro.spans``) while the profiler
traces, and ``run_cell`` traces only the window, so the program's log
holds the window's steps. Each ``engine.step`` record is one call of
``SparseDNNEngine.step`` that dispatched a panel. Inside it are its
children (``engine.stage``, ``engine.plan``, ``engine.dispatch``,
``engine.finite_sync``) and, on a plan miss, ``plan.build`` inside
``engine.plan``.
"""

from __future__ import annotations

import bisect

STEP = "engine.step"


def window_steps(view):
    """``[(step record, the records inside it)]`` of the traced window, or
    None: when the window ran nothing on a device (off the chip the sync
    waits on the host's own computation, not on a device), when the
    program records no spans (it has no ``repro.spans``), when its log is
    empty, and when the log's steps are not the window's panels one for
    one (a log left from an earlier window)."""
    if view.busy_s <= 0:
        return None
    try:
        from repro import spans
    except ImportError:
        return None
    records = spans.recorded()
    steps = [r for r in records if r.name == STEP]
    if not steps or len(steps) != view.counters.get("panels"):
        return None
    inner = sorted((r for r in records if r.name != STEP), key=lambda r: r.start_ns)
    starts = [r.start_ns for r in inner]
    out = []
    for step in steps:
        lo = bisect.bisect_left(starts, step.start_ns)
        hi = bisect.bisect_right(starts, step.end_ns)
        out.append((step, [r for r in inner[lo:hi] if r.end_ns <= step.end_ns]))
    return out


def span_ms(records, name: str) -> float:
    """Summed milliseconds of the records named ``name``."""
    return sum(r.ms for r in records if r.name == name)
