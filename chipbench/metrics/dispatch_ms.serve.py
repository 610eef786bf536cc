"""Median time from entry into the engine's step (the benchmark's span
around ``SparseDNNEngine.step``) to the start of the first Pallas kernel
inside it, in ms, on the trace's clock: the host path of a step (plan
lookup, padding, dispatch) and any device work queued ahead of it."""

import bisect

import numpy as np

STEP_SPAN = "chipbench.engine.step"


def read(view):
    starts = sorted(s for s, _, _, kernel in view.ops[0] if kernel)
    delays = []
    for s, e, name in view.spans:
        if name != STEP_SPAN or not (view.lo <= s < view.hi):
            continue
        i = bisect.bisect_left(starts, s)
        if i < len(starts) and starts[i] < e:
            delays.append((starts[i] - s) / 1e6)
    if not delays:
        return None
    return float(np.median(delays))
