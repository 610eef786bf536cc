"""Median time a step holds the host waiting for the device, in ms: the
program's ``engine.finite_sync`` span, the check that a panel's answers
are finite, which reads them back from the device."""

import numpy as np

from chipbench.yardstick import engine_spans

SYNC = "engine.finite_sync"


def read(view):
    steps = engine_spans.window_steps(view)
    if steps is None:
        return None
    syncs = [r.ms for _, inner in steps for r in inner if r.name == SYNC]
    if not syncs:
        return None
    return float(np.median(syncs)), f"{len(syncs)} syncs, max {max(syncs):.3f} ms"
