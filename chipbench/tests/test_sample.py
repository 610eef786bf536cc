"""The closed loop's sample: drawn uniformly without replacement from
every input the window completed, from the run's seed, never from a
failed panel, and kept on the device at a size that does not grow with
the window."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import drivers
from chipbench.tests._tiny import tiny_cell
from chipbench.yardstick import radixnet as yr
from chipbench.yardstick.reservoir import Reservoir

SEED = 2**31 + 29


def stream(size, width, panels, seed):
    """Pool index (input number) held by each slot after ``panels``
    panels of ``width`` completed inputs, drawn as the window draws."""
    sample = Reservoir(size, seed)
    for p in range(panels):
        cols, slots = sample.draw(width)
        assert np.all(np.diff(cols) > 0) and len(set(slots)) == len(slots)
        assert np.all((cols >= 0) & (cols < width) & (slots >= 0) & (slots < size))
        sample.admit(cols, slots, p * width, np.ones(width, bool))
    return sample


def test_each_input_is_kept_with_share_size_over_completed():
    """Over 3000 seeds each of 384 completed inputs is held by about
    size/completed of the samples: 40/384 = 0.104, with a binomial
    standard deviation of 0.0056 a share; 0.025 is four and a half of
    them, which no input of a fair sample reaches on these seeds."""
    size, width, panels, seeds = 40, 32, 12, 3000
    n = width * panels
    held = np.zeros(n)
    for s in range(seeds):
        sample = stream(size, width, panels, SEED + s)
        assert len(set(sample.cols)) == size
        held[sample.cols] += 1
    share = held / seeds
    assert np.abs(share - size / n).max() < 0.025, (share.min(), share.max())
    # early and late inputs alike: the first and last thirds hold the same share
    assert abs(share[: n // 3].mean() - share[-n // 3 :].mean()) < 0.005


@pytest.mark.parametrize("panels", [1, 2, 40])
def test_same_seed_same_sample_of_min_size_completed(panels):
    size, width = 40, 32
    a = stream(size, width, panels, SEED)
    b = stream(size, width, panels, SEED)
    assert a.seen == width * panels and a.filled == min(size, width * panels)
    assert np.array_equal(a.cols[: a.filled], b.cols[: b.filled])
    assert len(set(a.cols[: a.filled])) == a.filled
    other = stream(size, width, panels, SEED + 1)
    if width * panels > size:
        assert not np.array_equal(a.cols, other.cols)


class FailsPoolPanel:
    """The program's engine with every step over pool panel ``bad`` lost."""

    def __init__(self, engine, bad_panel):
        self._engine, self._bad = engine, np.asarray(bad_panel)

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self, *args, **kwargs):
        staged = np.asarray(jnp.concatenate([a for _, a in self._engine._staged], axis=1))
        out, stats = self._engine.step(*args, **kwargs)
        if out is not None and np.array_equal(staged, self._bad):
            return None, dict(stats, failed=True, error="planted")
        return out, stats


def test_failed_panels_are_never_admitted():
    cell = tiny_cell("challenge-1024x120")
    t = cell.traffic
    w = t["panel_width"]
    pool = yr.radixnet_input_panel(cell.config["neurons"], t["pool_inputs"],
                                   density=t["density"], seed=SEED)
    state = drivers.Closed.setup(
        cell, seed=SEED, seconds=0.2,
        wrap_engine=lambda engine: FailsPoolPanel(engine, pool[:, w : 2 * w]),
    )
    win = drivers.Closed.window(state, seconds=0.2)
    sample = win.answers[0]
    panels = win.counters["panels"]
    assert panels >= 6 and win.failed == w * (panels // 3 + (panels % 3 > 1))
    assert sample.seen == win.attempted - win.failed == win.counters["inputs"]
    assert sample.filled == cell.traffic["sample_inputs"]
    held = sample.cols[: sample.filled]
    assert not np.any((held >= w) & (held < 2 * w)), held
    y, mask, y0 = drivers.Closed.sampled(state, win, seed=SEED)
    assert y.shape == y0.shape == (cell.config["neurons"], sample.filled)


def _live_bytes():
    return sum(a.nbytes for a in jax.live_arrays())


def test_kept_device_bytes_do_not_grow_with_panels():
    """The window keeps the same device bytes after 0.2 s as after 0.6 s:
    the sample's fixed buffer, and no panel's output."""
    cell = tiny_cell("challenge-1024x120")
    grown, panels = [], []
    for seconds in (0.2, 0.6):
        state = drivers.Closed.setup(cell, seed=SEED, seconds=seconds)
        before = _live_bytes()
        win = drivers.Closed.window(state, seconds=seconds)
        grown.append(_live_bytes() - before)
        panels.append(win.counters["panels"])
        del state, win
    assert panels[1] >= 2 * panels[0] >= 4, panels
    assert grown[0] == grown[1] == 0, grown
