"""Continuous-batching scheduler: packing invariants, trace determinism,
ServeStats accounting against hand-computed values, starvation freedom,
and engine routing through the batcher."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dnn
from repro.serve import (
    ContinuousBatcher,
    RequestQueue,
    SparseDNNEngine,
    poissonish_trace,
    serve_trace_static,
)
from repro.sparse import BlockCSRMatrix, BlockSparseMatrix


def _stack(key, L, m, bpr=2, block=16):
    ks = jax.random.split(key, L)
    ws = [
        BlockSparseMatrix.random(k, (m, m), (block, block), blocks_per_row=bpr)
        for k in ks
    ]
    bs = [jnp.zeros((m,), jnp.float32) for _ in range(L)]
    return ws, bs


def _col(seed, m):
    return jax.random.uniform(jax.random.PRNGKey(seed), (m,), jnp.float32)


# ---------------------------------------------------------------------
# RequestQueue
# ---------------------------------------------------------------------


def test_queue_fifo_within_priority():
    q = RequestQueue()
    ids = [q.submit(_col(i, 8), now=0) for i in range(5)]
    got = [r.rid for r in q.pop_batch(3, now=0)]
    assert got == ids[:3]
    assert len(q) == 2


def test_queue_priority_and_deadline_order():
    q = RequestQueue()
    r_low = q.submit(_col(0, 8), now=0, priority=5)
    r_dead_late = q.submit(_col(1, 8), now=0, priority=1, deadline=90)
    r_dead_soon = q.submit(_col(2, 8), now=0, priority=1, deadline=10)
    r_urgent = q.submit(_col(3, 8), now=0, priority=0)
    got = [r.rid for r in q.pop_batch(4, now=0)]
    assert got == [r_urgent, r_dead_soon, r_dead_late, r_low]


def test_queue_aging_prevents_starvation():
    """A low-priority request overtakes a stream of fresh high-priority
    arrivals once it has aged enough — no request waits forever."""
    q = RequestQueue(age_every=4)
    old = q.submit(_col(0, 8), now=0, priority=3)
    # effective priority after waiting 12 ticks: 3 - 12//4 = 0, and the
    # older arrival breaks the tie against any fresh priority-0 request
    fresh = q.submit(_col(1, 8), now=12, priority=0)
    got = [r.rid for r in q.pop_batch(1, now=12)]
    assert got == [old] != [fresh]


# ---------------------------------------------------------------------
# packing invariants
# ---------------------------------------------------------------------


def test_batcher_packing_invariants():
    """slots ≤ batch_size; padded width is the smallest tile multiple
    covering the occupancy; every slot is tagged with its request id."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(0), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    b = ContinuousBatcher(eng, batch_size=4, min_fill=0.0, max_wait=0)
    rids = [b.submit(_col(10 + i, m)) for i in range(11)]
    while b.completed < 11:
        b.step(force=True)
    stats = b.stats()
    assert stats.requests == 11
    served = []
    for rec in stats.steps:
        assert 0 < rec.occupancy <= 4
        assert rec.padded_width == -(-rec.occupancy // 8) * 8
        assert rec.padded_width - rec.occupancy < 8
        assert len(rec.request_ids) == rec.occupancy
        served.extend(rec.request_ids)
    # every request served exactly once, in FIFO order for equal priority
    assert served == rids
    # capacity 4 over 11 requests → at least ceil(11/4) = 3 steps
    assert stats.engine_steps >= 3


def test_batcher_no_starvation_under_load():
    """A background-priority request completes despite a continuous
    stream of priority-0 arrivals saturating the batch each tick."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(1), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=4)
    b = ContinuousBatcher(eng, batch_size=2, min_fill=0.0, age_every=3)
    victim = b.submit(_col(0, m), priority=9)
    for t in range(40):
        b.submit(_col(100 + t, m), priority=0)
        b.submit(_col(200 + t, m), priority=0)
        b.step()
        if victim in b.stats().latencies:
            break
    assert victim in b.stats().latencies, "aged request never served"


def test_batcher_mid_flight_join_and_eviction():
    """Requests arriving between steps join the next panel; completed
    requests leave their slots (results retrievable, slots reused)."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(2), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=4)
    b = ContinuousBatcher(eng, batch_size=8, min_fill=0.0, max_wait=0)
    first = b.submit(_col(1, m))
    b.step()
    assert b.completed == 1  # evicted at the step boundary
    late = b.submit(_col(2, m))  # joins mid-stream, next panel
    rec = b.step()
    assert rec.request_ids == (late,)
    np.testing.assert_allclose(
        np.asarray(b.result(first)),
        np.asarray(
            dnn.dnn_forward(ws, bs, _col(1, m)[:, None], fused=True)[:, 0]
        ),
        rtol=1e-5,
        atol=1e-5,
    )


def test_min_fill_holds_then_max_wait_forces():
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(3), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=4)
    b = ContinuousBatcher(eng, batch_size=8, min_fill=0.5, max_wait=3)
    b.submit(_col(1, m))  # 1 < 0.5·8 → held
    assert b.step() is None
    assert b.step() is None
    assert b.step() is None
    rec = b.step()  # waited 3 ticks → forced out
    assert rec is not None and rec.occupancy == 1
    assert b.stats().latency_max == 4


# ---------------------------------------------------------------------
# trace determinism
# ---------------------------------------------------------------------


def test_poissonish_trace_deterministic():
    t1 = poissonish_trace(50, m=16, lam=2.5, burst_every=8, burst_size=6, seed=3)
    t2 = poissonish_trace(50, m=16, lam=2.5, burst_every=8, burst_size=6, seed=3)
    assert [len(a) for a in t1] == [len(a) for a in t2]
    assert sum(len(a) for a in t1) == 50
    for a1, a2 in zip(t1, t2):
        for c1, c2 in zip(a1, a2):
            np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    t3 = poissonish_trace(50, m=16, lam=2.5, burst_every=8, burst_size=6, seed=4)
    assert [len(a) for a in t1] != [len(a) for a in t3]


def test_trace_rejects_arrival_free_parameters():
    """lam=0 with no bursts can never terminate — must raise, not hang."""
    with pytest.raises(ValueError):
        poissonish_trace(10, m=8, lam=0.0)
    with pytest.raises(ValueError):
        poissonish_trace(10, m=8, lam=0.0, burst_every=0, burst_size=5)


def test_trace_bursts_land_on_schedule():
    trace = poissonish_trace(
        60, m=8, lam=0.0, burst_every=4, burst_size=5, seed=0
    )
    counts = [len(a) for a in trace]
    assert all(c == 0 for i, c in enumerate(counts) if i % 4 != 3)
    assert all(c == 5 for i, c in enumerate(counts) if i % 4 == 3)


# ---------------------------------------------------------------------
# ServeStats accounting vs hand-computed values
# ---------------------------------------------------------------------


def test_servestats_hand_computed():
    """3 + 1 requests, capacity 4, tile 8: one panel of width 8 holding
    4 rows → pad fraction 1 − 4/8, grid steps = L·nrb·mbpr·n_tiles."""
    m, L, bpr = 32, 2, 2
    ws, bs = _stack(jax.random.PRNGKey(4), L, m, bpr=bpr)
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    b = ContinuousBatcher(eng, batch_size=4, min_fill=1.0, max_wait=10)
    for i in range(3):
        b.submit(_col(i, m))
    b.step()  # 3 < capacity 4 and wait < 10 → held
    b.submit(_col(9, m))
    rec = b.step()  # 4 = capacity → dispatched
    assert rec.occupancy == 4 and rec.padded_width == 8
    s = b.stats()
    assert s.rows_served == 4
    assert s.padded_slots == 8
    assert s.pad_slot_fraction == pytest.approx(0.5)
    # grid steps: padded width 8 → one 8-wide tile; per layer nrb·mbpr
    nrb = m // 16
    expect = L * nrb * bpr * 1
    assert rec.grid_steps == expect == s.grid_steps_total
    assert s.grid_steps_per_row == pytest.approx(expect / 4)
    # latencies: 3 early requests waited one held tick (2), late one 1
    assert sorted(s.latencies.values()) == [1, 2, 2, 2]
    assert s.latency_mean == pytest.approx(7 / 4)
    assert s.latency_max == 2
    assert s.idle_ticks == 1  # the held tick


def test_deadline_miss_accounting():
    """A request that cannot make its deadline is SHED at packing time —
    a deadline miss, never a (late) completion. docs/robustness.md."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(5), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=4)
    b = ContinuousBatcher(eng, batch_size=4, min_fill=1.0, max_wait=5)
    rid0 = b.submit(_col(0, m), deadline=1)  # admissible at tick 0, but
    b.submit(_col(1, m), deadline=50)  # min_fill holds the panel...
    for _ in range(6):
        b.step()
    s = b.stats()
    # ...so at tick 1 its earliest completion is tick 2 > deadline 1:
    # shed as inadmissible, never dispatched, counted as a miss.
    assert s.requests == 1
    assert s.deadline_misses == 1
    assert s.faults.shed_inadmissible == 1
    assert s.faults.shed_expired == 0
    assert s.goodput == pytest.approx(0.5)
    assert "shed" in b.failures[rid0]
    assert rid0 not in s.latencies


def test_deadline_enforcement_off_serves_late():
    """enforce_deadlines=False restores the legacy record-only miss."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(5), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=4)
    b = ContinuousBatcher(
        eng, batch_size=4, min_fill=1.0, max_wait=5,
        enforce_deadlines=False,
    )
    b.submit(_col(0, m), deadline=1)
    b.submit(_col(1, m), deadline=50)
    for _ in range(6):
        b.step()
    s = b.stats()
    assert s.requests == 2  # served anyway, just late
    assert s.deadline_misses == 1
    assert s.faults.shed == 0
    assert s.faults.completed_late == 1
    assert s.goodput == pytest.approx(0.5)


def test_static_baseline_accounting():
    """Static aligned batching: every tick pays a full aligned panel."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(6), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=16)
    trace = [
        [_col(1, m)],
        [],
        [_col(2, m), _col(3, m)],
    ]
    s = serve_trace_static(eng, trace)
    assert s.engine_steps == 2  # empty tick dispatches nothing
    assert s.rows_served == 3
    assert s.padded_slots == 32  # two 16-wide aligned panels
    assert s.pad_slot_fraction == pytest.approx(1 - 3 / 32)
    assert all(v == 1 for v in s.latencies.values())


def test_continuous_beats_static_on_bursty_trace():
    """The acceptance-criterion shape, small: same weights, same trace,
    strictly lower pad-slot fraction and grid steps for continuous."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(7), 2, m)
    trace = poissonish_trace(
        40, m=m, lam=2.0, burst_every=6, burst_size=8, seed=11
    )
    static = serve_trace_static(
        SparseDNNEngine(ws, bs, batch_align=32), trace
    )
    b = ContinuousBatcher(
        SparseDNNEngine(ws, bs, batch_align=8),
        batch_size=32,
        min_fill=0.25,
        max_wait=3,
    )
    cont = b.run_trace(trace)
    assert cont.requests == static.requests == 40
    assert cont.pad_slot_fraction < static.pad_slot_fraction
    assert cont.grid_steps_total < static.grid_steps_total


# ---------------------------------------------------------------------
# engine routing through the batcher
# ---------------------------------------------------------------------


def test_batcher_routes_resident_path_when_eligible():
    """A homogeneous square stack serves layered through the batcher:
    one kernel call per layer, at the engine's preferred level."""
    m = 64
    ws, bs = _stack(jax.random.PRNGKey(8), 3, m)
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    b = ContinuousBatcher(eng, batch_size=8)
    rid = b.submit(_col(0, m))
    rec = b.step(force=True)
    assert rec.pallas_calls == 3  # one kernel call per layer
    assert rec.plan_level == "layered" and not rec.degraded
    np.testing.assert_allclose(
        np.asarray(b.result(rid)),
        np.asarray(
            dnn.dnn_forward(ws, bs, _col(0, m)[:, None], fused=True)[:, 0]
        ),
        rtol=1e-5,
        atol=1e-5,
    )


def test_batcher_layered_path_on_mixed_layout():
    m = 64
    ws, bs = _stack(jax.random.PRNGKey(9), 2, m)
    mixed = [BlockCSRMatrix.from_bsr(ws[0]), ws[1]]
    eng = SparseDNNEngine(mixed, bs, batch_align=8)
    b = ContinuousBatcher(eng, batch_size=8)
    b.submit(_col(0, m))
    rec = b.step(force=True)
    assert rec.pallas_calls == 2  # one kernel call per layer


def test_batcher_differentiable_engine():
    """differentiable=True engines keep the caller's layouts; the
    batcher serves them unchanged."""
    m = 64
    ws, bs = _stack(jax.random.PRNGKey(10), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=8, differentiable=True)
    b = ContinuousBatcher(eng, batch_size=8)
    rid = b.submit(_col(3, m))
    rec = b.step(force=True)
    assert rec.pallas_calls == 2
    np.testing.assert_allclose(
        np.asarray(b.result(rid)),
        np.asarray(
            dnn.dnn_forward(ws, bs, _col(3, m)[:, None], fused=True)[:, 0]
        ),
        rtol=1e-5,
        atol=1e-5,
    )


def test_batcher_outputs_match_reference_across_panels():
    """Every request's column equals the one-shot forward regardless of
    which panel it was packed into."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(11), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=4)
    b = ContinuousBatcher(eng, batch_size=3, min_fill=0.0)
    cols = {b.submit(_col(40 + i, m)): _col(40 + i, m) for i in range(7)}
    b.drain()
    for rid, col in cols.items():
        np.testing.assert_allclose(
            np.asarray(b.result(rid)),
            np.asarray(dnn.dnn_forward(ws, bs, col[:, None], fused=True)[:, 0]),
            rtol=1e-5,
            atol=1e-5,
        )
