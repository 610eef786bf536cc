"""Median host work of a step, in ms: the program's ``engine.step`` span
less its ``engine.finite_sync`` child, where the host waits for the
device; what is left is staging, the plan lookup, the dispatch and the
step's own bookkeeping. The note gives the median of each child span and of the rest of the
step, the largest step, and the plan misses, plan builds and dispatches
that compiled in the window."""

import numpy as np

from chipbench.yardstick import engine_spans

CHILDREN = ("engine.stage", "engine.plan", "engine.dispatch", "engine.finite_sync")


def read(view):
    steps = engine_spans.window_steps(view)
    if steps is None:
        return None
    host = [s.ms - engine_spans.span_ms(c, "engine.finite_sync") for s, c in steps]
    parts = {
        name: np.median([engine_spans.span_ms(c, name) for _, c in steps])
        for name in CHILDREN
    }
    rest = np.median([s.ms - sum(engine_spans.span_ms(c, n) for n in CHILDREN) for s, c in steps])
    inner = [r for _, rs in steps for r in rs]
    misses = sum(1 for r in inner if r.name == "engine.plan" and not r.attrs.get("hit"))
    builds = [r.ms for r in inner if r.name == "plan.build"]
    compiled = sum(1 for r in inner if r.name == "engine.dispatch" and r.attrs.get("compiled"))
    medians = ", ".join(f"{n.split('.', 1)[1]} {v:.3f}" for n, v in parts.items())
    return float(np.median(host)), (
        f"{len(steps)} steps; median ms: {medians}, rest {rest:.3f}; largest step "
        f"less sync {max(host):.3f} ms; {misses} plan misses, {len(builds)} plan builds "
        f"({sum(builds):.3f} ms), {compiled} dispatches that compiled"
    )
