"""Host spans (``repro.spans``): recorded only while the profiler traces,
nested per thread, bounded; and the spans a traced ``SparseDNNEngine``
step opens."""

import glob
import threading

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.serve.engine import SparseDNNEngine
from repro.sparse.bsr import BlockSparseMatrix


@pytest.fixture
def log():
    """An empty span log, emptied again after the test."""
    spans.take()
    yield spans
    spans.take()


@pytest.fixture
def tracing(tmp_path):
    """The profiler tracing into ``tmp_path`` for the test's length."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        yield tmp_path
    finally:
        jax.profiler.stop_trace()


def test_nothing_is_recorded_while_the_profiler_is_off(log):
    with spans.span("outer", a=1) as s:
        s.set(b=2)
        with spans.span("inner"):
            pass
    assert spans.recorded() == []
    assert spans.span("x") is spans.span("y")  # one shared no-op context


def test_nesting_parents_and_attributes_set_at_exit(log, tracing):
    with spans.span("outer", a=1) as outer:
        with spans.span("inner", k=3) as inner:
            inner.set(hit=True)
        with spans.span("inner"):
            pass
        outer.set(done="yes")
    with spans.span("after"):
        pass
    recs = spans.recorded()
    # a span is logged when it closes: children before their parent
    assert [(r.name, r.parent) for r in recs] == [
        ("inner", "outer"),
        ("inner", "outer"),
        ("outer", None),
        ("after", None),
    ]
    assert recs[0].attrs == {"k": 3, "hit": True}
    assert recs[1].attrs == {}
    assert recs[2].attrs == {"a": 1, "done": "yes"}
    inner1, inner2, outer_rec, after = recs
    assert outer_rec.start_ns <= inner1.start_ns <= inner1.end_ns
    assert inner1.end_ns <= inner2.start_ns <= inner2.end_ns <= outer_rec.end_ns
    assert outer_rec.end_ns <= after.start_ns
    assert all(r.ms >= 0 for r in recs)


def test_parents_are_tracked_per_thread(log, tracing):
    started, release = threading.Event(), threading.Event()

    def other():
        with spans.span("thread.outer"):
            started.set()
            release.wait(timeout=10)
            with spans.span("thread.inner"):
                pass

    t = threading.Thread(target=other)
    with spans.span("main.outer"):
        t.start()
        assert started.wait(timeout=10)
        with spans.span("main.inner"):
            pass
        release.set()
        t.join(timeout=10)
    assert not t.is_alive()
    parents = {r.name: r.parent for r in spans.recorded()}
    assert parents == {
        "main.inner": "main.outer",
        "thread.inner": "thread.outer",
        "thread.outer": None,
        "main.outer": None,
    }


def test_take_returns_and_clears(log, tracing):
    with spans.span("a"):
        pass
    with spans.span("b"):
        pass
    taken = spans.take()
    assert [r.name for r in taken] == ["a", "b"]
    assert spans.recorded() == [] and spans.take() == []


def test_the_log_is_bounded_and_counts_what_it_drops():
    log = spans.SpanLog(max_records=3)
    for i in range(5):
        log.append(spans.Record(f"s{i}", None, i, i + 1, {}))
    assert [r.name for r in log.recorded()] == ["s0", "s1", "s2"]
    assert log.dropped == 2
    assert len(log.take()) == 3
    assert log.recorded() == [] and log.dropped == 0


def test_spans_land_in_the_profiler_trace(log, tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with spans.span("engine.plan", width=64) as s:
            s.set(hit=False)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [
        ev
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines
        for ev in line.events
        if ev.name == "repro.engine.plan"
    ]
    assert len(events) == 1
    assert dict(events[0].stats) == {"width": 64, "hit": 0}


def _engine():
    m = 32
    ks = jax.random.split(jax.random.key(70), 2)
    ws = [
        BlockSparseMatrix.random(k, (m, m), (16, 16), blocks_per_row=2)
        for k in ks
    ]
    bs = [jnp.zeros((m,), jnp.float32) for _ in ws]
    return SparseDNNEngine(ws, bs, batch_align=8), m


def test_a_traced_engine_step_records_its_parts(log, tracing):
    eng, m = _engine()
    cols = jax.random.uniform(jax.random.key(71), (m, 5))
    for _ in range(2):
        eng.submit(cols[:, :3])
        eng.submit(cols[:, 3:])
        out, stats = eng.step()
        assert out.shape == (m, 5) and not stats["failed"]
    recs = spans.recorded()
    steps = [r for r in recs if r.name == "engine.step"]
    assert [r.attrs for r in steps] == [
        {"ordinal": 0, "batch": 5, "width": 8},
        {"ordinal": 1, "batch": 5, "width": 8},
    ]
    children = [
        [
            r
            for r in recs
            if r.parent == "engine.step" and st.start_ns <= r.start_ns <= st.end_ns
        ]
        for st in steps
    ]
    order = ["engine.stage", "engine.plan", "engine.dispatch", "engine.finite_sync"]
    for kids in children:
        assert [r.name for r in kids] == order
        assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))
    first, second = ({r.name: r.attrs for r in kids} for kids in children)
    assert first["engine.stage"] == second["engine.stage"] == {"chunks": 2}
    level = eng.ladder.preferred_level
    assert first["engine.plan"] == {"width": 8, "level": level, "hit": False}
    assert first["engine.dispatch"] == {"compiled": True}
    assert second["engine.plan"] == {"width": 8, "level": level, "hit": True}
    assert second["engine.dispatch"] == {"compiled": False}
    builds = [r for r in recs if r.name == "plan.build"]
    assert [(r.parent, r.attrs) for r in builds] == [
        (
            "engine.plan",
            {"width": 8, "route": "layered", "component_layers": 0,
             "weight_args": 2},
        )
    ]
    assert steps[0].start_ns <= builds[0].start_ns <= builds[0].end_ns <= steps[0].end_ns


def test_plan_build_counts_the_component_layers(log, tracing):
    from repro.data import radixnet as rx

    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(256, 5))
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    eng.infer(jnp.ones((256, 4)))
    (build,) = [r for r in spans.recorded() if r.name == "plan.build"]
    assert build.attrs == {
        "width": 8, "route": "layered", "component_layers": 5, "weight_args": 2
    }


def test_an_auto_resident_engine_over_components_builds_layered(log, tracing):
    """An engine over a 1024 x 3 RadiX-net serves at the layered level,
    whose plan runs the component layout."""
    from repro.data import radixnet as rx

    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(1024, 3))
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    assert eng.ladder.preferred_level == "layered"
    out, stats = eng.infer(jnp.ones((1024, 4)))
    assert stats["plan"]["level"] == "layered"
    assert stats["plan"]["route"] == "layered"
    (plan,) = eng.plan_cache.plans()
    assert plan.route == "layered" and plan.component_layers == 3
    (build,) = [r for r in spans.recorded() if r.name == "plan.build"]
    assert build.attrs == {
        "width": 8, "route": "layered", "component_layers": 3, "weight_args": 2
    }


def test_an_idle_step_opens_no_span(log, tracing):
    eng, _ = _engine()
    out, _ = eng.step()
    assert out is None and spans.recorded() == []


def test_no_finite_sync_span_without_the_quarantine(log, tracing):
    eng, m = _engine()
    eng.quarantine_nonfinite = False
    eng.infer(jnp.ones((m, 4)))
    names = [r.name for r in spans.recorded()]
    assert "engine.finite_sync" not in names and "engine.dispatch" in names
