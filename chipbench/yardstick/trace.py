"""Reduction of a profiler trace to what the per-layer readers need.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Each chip is a plane named ``/device:TPU:<n>`` whose ``XLA
Ops`` line holds one event per operation run on the device. The host
plane holds the benchmark's own spans (``chipbench.*``, from
``drivers.span``) on the same clock. The window is the
``chipbench.window`` span.

Busy time is the union of the device's operation intervals inside the
window, averaged over the chips used. On the TPU an event's name is the
text of its HLO instruction (``%bcsr_spmm.92 = f32[16384,512]{...}
custom-call(...), custom_call_target="tpu_custom_call", ...``); the
event's own stats carry no HLO category. A Pallas kernel is an operation
whose custom-call target is ``tpu_custom_call`` (the program's kernels
have no distinct names yet, so every such call counts as kernel time).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"


KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
INSTANCE = re.compile(r"\.\d+$")


def is_kernel(name: str) -> bool:
    """A Pallas kernel launch: a custom call to the TPU's kernel target."""
    return KERNEL_TARGET in name


def op_name(name: str) -> str:
    """An HLO instruction's name without its instance number:
    ``%bcsr_spmm.92 = f32[...] custom-call(...)`` reads ``bcsr_spmm``."""
    return INSTANCE.sub("", name.split(" = ", 1)[0].lstrip("%"))


def union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``(start, end)`` intervals clipped to [lo, hi]."""
    total = 0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle stretches of [lo, hi] that no interval covers."""
    out = []
    at = lo
    for s, e in sorted(intervals):
        if e <= at:
            continue
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class TraceWindow:
    """One traced window, reduced; the object every reader receives."""

    cell: Any
    peaks: dict
    chips: int
    lo: int  # window start and end, ns on the trace clock
    hi: int
    ops: list  # per chip: [(start, end, name, is_kernel)]
    spans: list  # [(start, end, name)] of the benchmark's own spans
    counters: dict

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_ns(self, chip: int) -> int:
        return union_ns([(s, e) for s, e, _, _ in self.ops[chip]], self.lo, self.hi)

    @property
    def busy_s(self) -> float:
        return sum(self.busy_ns(c) for c in range(self.chips)) / self.chips / 1e9

    def kernel_s(self) -> float:
        """Summed device time of every kernel event in the window, all chips."""
        return sum(
            min(e, self.hi) - max(s, self.lo)
            for chip in self.ops
            for s, e, _, k in chip
            if k and e > self.lo and s < self.hi
        ) / 1e9

    def innermost_span(self, at: int) -> str:
        best = None
        for s, e, name in self.spans:
            if s <= at < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2][len(SPAN_PREFIX) :] if best else "outside any span"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, summed over every
        instance of one HLO name (the 120 ``bcsr_spmm.<n>`` launches of a
        panel read as one ``bcsr_spmm``), and the longest idle gaps by
        the innermost benchmark span the host was in."""
        by_op: dict[str, float] = {}
        for chip in self.ops:
            for s, e, name, _ in chip:
                if e > self.lo and s < self.hi:
                    name = op_name(name)
                    by_op[name] = by_op.get(name, 0.0) + (
                        min(e, self.hi) - max(s, self.lo)
                    ) / 1e9
        by_span: dict[str, float] = {}
        for c, chip in enumerate(self.ops):
            for s, e in gaps_ns([(a, b) for a, b, _, _ in chip], self.lo, self.hi):
                label = self.innermost_span((s + e) // 2)
                by_span[label] = by_span.get(label, 0.0) + (e - s) / 1e9 / self.chips
        order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
        return {
            "device_ops": [[k, v] for k, v in order(by_op)],
            "idle_gaps": [[k, v] for k, v in order(by_span)],
        }


def reduce_profile(data, chips: int):
    """(ops per chip, benchmark spans) of a ``jax.profiler.ProfileData``."""
    ops: dict[int, list] = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chip = ops.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    chip.append((ev.start_ns, ev.end_ns, ev.name, is_kernel(ev.name)))
            elif not m:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.end_ns, ev.name))
    used = [ops.get(c, []) for c in sorted(ops)[:chips]] if ops else [[]] * chips
    return used, spans


def load_window(trace_dir: str, *, chips: int, cell, peaks: dict, counters: dict) -> TraceWindow:
    """The traced window of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file under {trace_dir}, found {files}")
    return window_of(
        ProfileData.from_file(files[0]), chips=chips, cell=cell, peaks=peaks, counters=counters
    )


def window_of(data, *, chips: int, cell, peaks: dict, counters: dict) -> TraceWindow:
    """The traced window of a ``jax.profiler.ProfileData``."""
    ops, spans = reduce_profile(data, chips)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(windows)}")
    (lo, hi), = windows
    return TraceWindow(cell, peaks, chips, lo, hi, ops, spans, counters)
