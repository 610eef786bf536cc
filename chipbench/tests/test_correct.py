"""``correct`` holds for the program as it stands, and comes out false
with each fault the cells can have planted in the timed path and with
the control in the program's place."""

import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.tests._tiny import run, tiny_cell
from chipbench.yardstick import radixnet as yr
from chipbench.yardstick.check import compare_columns

CELLS = ["challenge-16384x120", "challenge-1024x120", "serve-1024x120-open"]


class Broken:
    """The program's engine with its step's answers changed by ``fault``."""

    def __init__(self, engine, fault):
        self._engine, self._fault = engine, fault

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def step(self, *args, **kwargs):
        staged = jnp.concatenate([a for _, a in self._engine._staged], axis=1)
        out, stats = self._engine.step(*args, **kwargs)
        if out is None:
            return out, stats
        return self._fault(out, staged, stats)


def altered(out, staged, stats):  # each answer handed to its neighbour's request
    pairs = jnp.arange(out.shape[1]) ^ 1
    return out[:, jnp.minimum(pairs, out.shape[1] - 1)], stats


def half_left_out(out, staged, stats):  # the back half of the batch never computed
    return out.at[:, out.shape[1] // 2 :].set(0.0), stats


def lost(out, staged, stats):  # the step fails and its answers never come
    return None, dict(stats, failed=True, error="planted")


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    res = run(tiny_cell(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["altered", "half_left_out", "lost"])
def test_broken_timed_path_is_not_correct(name, fault):
    plant = globals()[fault]
    res = run(tiny_cell(name), wrap_engine=lambda engine: Broken(engine, plant))
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", ["challenge-16384x120", "challenge-1024x120"])
def test_control_is_not_correct(name):
    """The control -- the reference with the three-pass (``high``)
    product's operands, the precision below the float32 at ``highest``
    that the configuration states -- in the program's place, at the
    configuration's full size over as many inputs as a run samples."""
    cell = harness.load_cell(name)
    cfg, t = cell.config, cell.traffic
    y0 = yr.radixnet_input_panel(cfg["neurons"], t["sample_inputs"], density=t["density"],
                                 seed=2**31 + 1)
    ref = yr.stack_reference(cfg["neurons"], cfg["layers"], cfg["bias"], y0, chunk=16)
    ctl = yr.stack_reference(cfg["neurons"], cfg["layers"], cfg["bias"], y0, chunk=16,
                             operands="high")
    checks = compare_columns(ctl, ctl.max(axis=0) > 0, ref, cfg["limits"], lost=0)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks
