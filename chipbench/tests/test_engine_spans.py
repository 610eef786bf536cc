"""The readers of the engine's own spans: ``engine_host_ms`` and
``engine_sync_ms`` on a synthetic log, and every case in which they read
nothing."""

import sys

import pytest
from repro import spans

from chipbench import harness

MS = 1_000_000  # ns
# (stage, plan, dispatch, finite_sync, rest) in ms of three steps
STEPS = [
    (0.1, 30.0, 40.0, 200.0, 0.5),
    (0.1, 0.2, 1.0, 210.0, 0.3),
    (0.2, 0.3, 2.0, 190.0, 0.4),
]
CHILDREN = ("engine.stage", "engine.plan", "engine.dispatch", "engine.finite_sync")


def synthetic_log():
    log = spans.SpanLog()
    t = 0
    for i, parts in enumerate(STEPS):
        start = t
        for name, ms in zip(CHILDREN, parts):
            attrs = {"engine.plan": {"hit": i > 0}, "engine.dispatch": {"compiled": i == 0}}
            if name == "engine.plan" and i == 0:  # the miss builds the plan
                log.append(spans.Record("plan.build", name, t + MS, t + 26 * MS, {}))
            log.append(spans.Record(name, "engine.step", t, t + int(ms * MS), attrs.get(name, {})))
            t += int(ms * MS)
        t += int(parts[-1] * MS)
        log.append(spans.Record("engine.step", None, start, t, {"ordinal": i}))
        t += 5 * MS  # between steps: the traffic loop's own work
    return log


class View:
    def __init__(self, panels, busy_s=1.0):
        self.counters = {"panels": panels}
        self.busy_s = busy_s


@pytest.fixture
def logged(monkeypatch):
    monkeypatch.setattr(spans, "LOG", synthetic_log())


def read(metric, view):
    return harness.load_reader(metric).read(view)


def test_engine_host_ms_is_the_step_less_its_sync(logged):
    value, note = read("engine_host_ms", View(3))
    # 30.1+0.5+40 = 70.6, 0.1+0.2+1+0.3 = 1.6, 0.2+0.3+2+0.4 = 2.9
    assert value == pytest.approx(2.9)
    assert "3 steps" in note
    assert "stage 0.100, plan 0.300, dispatch 2.000, finite_sync 200.000, rest 0.400" in note
    assert "largest step less sync 70.600 ms" in note
    assert "1 plan misses, 1 plan builds (25.000 ms), 1 dispatches that compiled" in note


def test_engine_sync_ms_is_the_median_sync(logged):
    value, note = read("engine_sync_ms", View(3))
    assert value == pytest.approx(200.0)
    assert note.startswith("3 syncs")


@pytest.mark.parametrize("metric", ["engine_host_ms", "engine_sync_ms"])
@pytest.mark.parametrize("panels", [2, 4, None])
def test_nothing_when_the_steps_are_not_the_windows_panels(logged, metric, panels):
    """A log left from another window reads as nothing."""
    view = View(3)
    view.counters = {} if panels is None else {"panels": panels}
    assert read(metric, view) is None


@pytest.mark.parametrize("metric", ["engine_host_ms", "engine_sync_ms"])
def test_nothing_from_a_window_with_no_device_work(logged, metric):
    """Off the chip the trace has no device plane: no wait is on a device."""
    assert read(metric, View(3, busy_s=0.0)) is None


@pytest.mark.parametrize("metric", ["engine_host_ms", "engine_sync_ms"])
def test_nothing_from_an_empty_log(monkeypatch, metric):
    monkeypatch.setattr(spans, "LOG", spans.SpanLog())
    assert read(metric, View(0)) is None
    assert read(metric, View(3)) is None


@pytest.mark.parametrize("metric", ["engine_host_ms", "engine_sync_ms"])
def test_nothing_from_a_program_without_spans(logged, monkeypatch, metric):
    """The program of an earlier commit has no ``repro.spans``."""
    import repro

    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert read(metric, View(3)) is None


def test_no_sync_reads_no_sync_time(monkeypatch):
    log = spans.SpanLog()
    log.append(spans.Record("engine.dispatch", "engine.step", 1 * MS, 2 * MS, {}))
    log.append(spans.Record("engine.step", None, 0, 3 * MS, {}))
    monkeypatch.setattr(spans, "LOG", log)
    assert read("engine_sync_ms", View(1)) is None
    assert read("engine_host_ms", View(1))[0] == pytest.approx(3.0)


def test_children_belong_to_the_step_that_holds_them(monkeypatch):
    """A step's children are the spans inside its interval, not those of
    the step before or after it."""
    log = spans.SpanLog()
    for start in (0, 10 * MS):
        sync = spans.Record("engine.finite_sync", "engine.step", start + MS, start + 5 * MS, {})
        log.append(sync)
        log.append(spans.Record("engine.step", None, start, start + 6 * MS, {}))
    log.append(spans.Record("engine.finite_sync", "engine.step", 7 * MS, 9 * MS, {}))
    monkeypatch.setattr(spans, "LOG", log)
    assert read("engine_host_ms", View(2))[0] == pytest.approx(2.0)
    assert read("engine_sync_ms", View(2))[0] == pytest.approx(4.0)
