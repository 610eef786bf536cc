"""A cell of the benchmark cut to a size the CPU runs in a second, with
the Pallas kernels in interpret mode: the same harness, drivers, engine
and comparison, and the real cell's limits."""

import dataclasses
import time

from chipbench import harness

TINY = {
    "closed": dict(panel_width=32, pool_inputs=96, sample_inputs=40),
    "open": dict(rate_per_s=300, batch_size=16, sample_inputs=40),
}
# Twelve layers, not six: after six the sampled activations still sit near
# the ReLU threshold, and their gap swings past the real limits on about
# one seed in ten; by twelve they grow as at full depth.
LAYERS = 12
NO_CHIP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
# (configuration, traffic mix) of each cell the tests cut down; the served
# mix has no cell in BENCHMARK.json until its rate is measured
MIXES = {
    "challenge-16384x120": ("radixnet-16384x120", "stream512-d0.4"),
    "challenge-1024x120": ("radixnet-1024x120", "stream512-d0.3"),
    "serve-1024x120-open": ("radixnet-1024x120", "open-poisson-d0.3"),
}


def tiny_cell(name: str) -> harness.Cell:
    cell = harness.cell_of(*MIXES[name], name=name)
    return dataclasses.replace(
        cell,
        config=dict(cell.config, neurons=64, layers=LAYERS),
        traffic=dict(cell.traffic, **TINY[cell.traffic["loop"]]),
    )


def run(cell, *, seed=2**31 + 17, seconds=0.2, trace=False, wrap_engine=None):
    """A run past the harness's look for a chip (``run_cell``)."""
    return harness.run_cell(
        cell, seed=seed, seconds=seconds, trace=trace, t0=time.perf_counter(),
        device=NO_CHIP, wrap_engine=wrap_engine,
    )
