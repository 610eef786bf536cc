"""The reduction from a profiler trace to the per-layer metrics."""

import os

import pytest

from chipbench import harness
from chipbench.yardstick import peaks, trace

HERE = os.path.dirname(os.path.abspath(__file__))
V5E = peaks.peaks("TPU v5 lite")


def test_union_and_gaps_clip_to_the_window():
    ivs = [(0, 10), (5, 20), (30, 40), (38, 45), (90, 120)]
    assert trace.union_ns(ivs, 10, 100) == 10 + 15 + 10
    assert trace.gaps_ns(ivs, 10, 100) == [(20, 30), (45, 90)]
    assert trace.gaps_ns([], 0, 5) == [(0, 5)]


SYNTHETIC = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 8000000 duration_ps: 1000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fused_kernel.3 = f32[1024,512]{1,0} custom-call(f32[1024,512]{1,0} %p.1), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%copy.1 = f32[1024,512]{1,0} copy(f32[1024,512]{1,0} %p.1)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_run" } }
  event_metadata { key: 4 value { id: 4 name: "%fused_kernel.7 = f32[1024,512]{1,0} custom-call(f32[1024,512]{1,0} %p.2), custom_call_target=\\"tpu_custom_call\\"" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "chipbench.window" } }
  event_metadata { key: 2 value { id: 2 name: "chipbench.mask_readback" } }
  event_metadata { key: 3 value { id: 3 name: "chipbench.engine.step" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(run)" } }
}
"""


def synthetic_view(counters):
    from jax.profiler import ProfileData

    cell = harness.load_cell("challenge-1024x120")
    return trace.window_of(ProfileData.from_text_proto(SYNTHETIC), chips=1, cell=cell,
                           peaks=V5E, counters=counters)


def test_synthetic_window():
    """Window 10 us; ops busy 4 + 1 + 1 us; kernels 4 + 1 us."""
    view = synthetic_view({"inputs": 512})
    assert view.window_s == pytest.approx(10e-6)
    assert view.busy_s == pytest.approx(6e-6)
    assert view.kernel_s() == pytest.approx(5e-6)
    b = view.breakdown()
    assert b["device_ops"] == [["fused_kernel", pytest.approx(5e-6)], ["copy", pytest.approx(1e-6)]]
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"mask_readback": 1e-6, "engine.step": 2e-6, "window": 1e-6}
    )
    idle = harness.load_reader("device_idle_share.challenge").read(view)
    assert idle == pytest.approx(40.0)


def test_readers_on_the_synthetic_window():
    view = synthetic_view({"inputs": 512})
    share, note = harness.load_reader("spmm_roofline").read(view)
    flops = 2 * 32 * 1024 * 120 * 512
    assert share == pytest.approx(100 * flops / 197e12 / 5e-6)
    assert note.startswith("compute bound")
    mfu = harness.load_reader("step_mfu").read(view)
    assert mfu == pytest.approx(100 * flops / 10e-6 / 197e12)
    # entry into the engine's step at 6 us; the kernel inside it starts at 8 us
    assert harness.load_reader("dispatch_ms.serve").read(view) == pytest.approx(2e-3)


def test_readers_return_nothing_without_their_inputs():
    view = synthetic_view({})
    for name in ("spmm_roofline", "step_mfu", "queue_wait_ms.serve"):
        assert harness.load_reader(name).read(view) is None


# Traces recorded in a `--trace 1` run on one TPU v5e chip, trimmed by
# chipbench/tools/trim_trace.py to the first 60 device operations.
RECORDED = {"challenge-1024x120": "fused_mlp_forward", "challenge-16384x120": "bcsr_spmm"}


@pytest.mark.parametrize("cell,kernel", sorted(RECORDED.items()))
def test_recorded_chip_trace(cell, kernel):
    """Kernel launches are found by their custom-call target, not by any
    HLO category, and read under their HLO name in the breakdown."""
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "traces", f"{cell}.textproto")) as fh:
        data = ProfileData.from_text_proto(fh.read())
    view = trace.window_of(data, chips=1, cell=harness.load_cell(cell), peaks=V5E,
                           counters={"inputs": 512})
    assert 0 < view.kernel_s() < view.busy_s <= view.window_s
    ops = dict(view.breakdown()["device_ops"])
    assert max(ops, key=ops.get) == kernel
    assert ops[kernel] == pytest.approx(view.kernel_s())
    share, note = harness.load_reader("spmm_roofline").read(view)
    assert 0 < share < 100 and note.startswith("compute bound")
