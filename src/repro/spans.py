"""Host spans inside the program, on the profiler's clock.

``span(name, **attrs)`` marks a stretch of host work::

    with spans.span("engine.plan", width=512) as s:
        plan, hit = lookup()
        s.set(hit=hit)  # attributes known only at exit

Recording follows ``jax.profiler``'s own switch. While the profiler is not
tracing, ``span`` checks that switch once and returns a shared context
that does nothing. While it traces (``jax.profiler.trace``,
``start_trace``), a span

* enters ``jax.profiler.TraceAnnotation("repro.<name>", **attrs)``, so it
  lands in the trace on the same clock as the device's operations, with
  its attributes as the event's stats; and
* appends a :class:`Record` to an in-memory log on
  ``time.perf_counter_ns``, with the name of the span it opened inside
  (its parent, tracked per thread).

The log keeps at most ``MAX_RECORDS`` records; past that it counts the
records it dropped and keeps what it has. ``recorded()`` returns the log,
``take()`` returns it and clears it. Counters are spans' attributes,
counted from the log. ``docs/serving.md`` lists the spans the engine and
the plan cache open, and what reads each.
"""

from __future__ import annotations

import threading
import time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

PREFIX = "repro."
MAX_RECORDS = 65536


class Record(NamedTuple):
    """One closed span: times in ns on ``time.perf_counter_ns``."""

    name: str
    parent: str | None  # the span this one opened inside, on its thread
    start_ns: int
    end_ns: int
    attrs: dict

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6


class SpanLog:
    """A bounded, thread-safe list of closed spans."""

    def __init__(self, max_records: int = MAX_RECORDS):
        self.max_records = max_records
        self.dropped = 0
        self._records: list[Record] = []
        self._lock = threading.Lock()

    def append(self, record: Record) -> None:
        with self._lock:
            if len(self._records) < self.max_records:
                self._records.append(record)
            else:
                self.dropped += 1

    def recorded(self) -> list[Record]:
        with self._lock:
            return list(self._records)

    def take(self) -> list[Record]:
        """The records, and an empty log (the dropped count restarts)."""
        with self._lock:
            out, self._records, self.dropped = self._records, [], 0
            return out


LOG = SpanLog()
_open = threading.local()  # .stack: the spans open on this thread


class _Off:
    """The span while the profiler is not tracing."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs: Any) -> None:
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("name", "attrs", "parent", "start_ns", "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.parent = stack[-1].name if stack else None
        stack.append(self)
        self._annotation = TraceAnnotation(PREFIX + self.name, **self.attrs)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def set(self, **attrs: Any) -> None:
        """Attributes known only now; they close with the span."""
        self.attrs.update(attrs)
        self._annotation.set_metadata(**attrs)

    def __exit__(self, *exc):
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _open.stack.pop()
        LOG.append(Record(self.name, self.parent, self.start_ns, end_ns, self.attrs))
        return False


def span(name: str, **attrs: Any):
    """A span named ``repro.<name>`` while the profiler traces, else a no-op."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, attrs)


def recorded() -> list[Record]:
    """The spans closed while the profiler traced, oldest first."""
    return LOG.recorded()


def take() -> list[Record]:
    """``recorded()``, and clear the log."""
    return LOG.take()
