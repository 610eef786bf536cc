"""Median time a request waited, in ms: from its scheduled arrival to the
start of the batcher step that took it (the benchmark's span around
``ContinuousBatcher.step``)."""

import numpy as np


def read(view):
    waits = view.counters.get("queue_wait_ms")
    if waits is None or not len(waits):
        return None
    return float(np.median(waits))
