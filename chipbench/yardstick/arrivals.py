"""Open-loop arrival times, the benchmark's own copy of the program's
Lewis-Shedler draw (``repro.serve.loadgen.generate_jobs``, times only).

Every seed of a cell gets the same set of gaps between arrivals, drawn
once from a fixed stream, in an order the run's seed permutes: the
number of requests and the offered load are then the same in every run,
and only their order differs.
"""

from __future__ import annotations

import numpy as np

BASE_SEED = 0  # the one stream every seed's gaps are drawn from


def poisson_times(rate: float, duration: float, seed: int) -> np.ndarray:
    """Arrival times in [0, duration) of a Poisson process at ``rate``
    per second: Lewis-Shedler's candidate stream, which a constant
    profile never thins."""
    if rate <= 0 or duration <= 0:
        raise ValueError(f"need rate > 0 and duration > 0, got {rate}, {duration}")
    rng = np.random.default_rng(seed)
    times = []
    t = float(rng.exponential(1.0 / rate))
    while t < duration:
        times.append(t)
        t += float(rng.exponential(1.0 / rate))
    return np.asarray(times, dtype=np.float64)


def permuted_arrivals(rate: float, duration: float, seed: int) -> np.ndarray:
    """The base stream's gaps in the order ``seed`` draws, as times."""
    base = poisson_times(rate, duration, BASE_SEED)
    gaps = np.diff(base, prepend=0.0)
    order = np.random.default_rng(seed).permutation(len(gaps))
    return np.cumsum(gaps[order])
