"""Fault-injection harness + graceful degradation (docs/robustness.md):
the injector's scheduled-pop determinism, per-request NaN quarantine,
transient retry / graceful panel failure, cache-eviction storms,
plan-compile demotion down the degradation ladder, shard failure →
single-device fallback with identical results, and the batcher-level
fault accounting (backpressure, shedding, straggler ticks, goodput)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.plan import LEVEL_LAYERED, LEVEL_SHARDED
from repro.serve import ContinuousBatcher, QueueFull, SparseDNNEngine
from repro.sparse import BlockCSRMatrix, BlockSparseMatrix
from repro.testing import (
    SITE_CACHE_EVICTION,
    SITE_PANEL_NANS,
    SITE_PLAN_COMPILE,
    SITE_SHARD_FAILURE,
    SITE_STEP_TRANSIENT,
    SITE_STRAGGLER,
    FaultInjector,
    poison_panel,
)


def _bsr_stack(seed, L, m, bpr=2, block=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), L)
    ws = [
        BlockSparseMatrix.random(k, (m, m), (block, block), blocks_per_row=bpr)
        for k in ks
    ]
    bs = [jnp.zeros((m,), jnp.float32) for _ in range(L)]
    return ws, bs


def _csr_stack(seed, L, m, bpr=2, block=16):
    ws, bs = _bsr_stack(seed, L, m, bpr=bpr, block=block)
    return [BlockCSRMatrix.from_bsr(w) for w in ws], bs


def _panel(seed, m, k):
    return jax.random.uniform(jax.random.PRNGKey(seed), (m, k), jnp.float32)


def _col(seed, m):
    return jax.random.uniform(jax.random.PRNGKey(seed), (m,), jnp.float32)


# ---------------------------------------------------------------------
# FaultInjector mechanics
# ---------------------------------------------------------------------


def test_injector_scheduled_pop_and_log():
    inj = FaultInjector(seed=3)
    inj.schedule(SITE_PANEL_NANS, 2, count=1)
    inj.schedule(SITE_PANEL_NANS, 2, count=2)  # second fault, same slot
    assert inj.pending() == 2
    assert inj.fires(SITE_PANEL_NANS, 0) is None  # wrong ordinal
    assert inj.fires(SITE_STEP_TRANSIENT, 2) is None  # wrong site
    assert inj.fires(SITE_PANEL_NANS, 2) == {"count": 1}  # schedule order
    assert inj.fires(SITE_PANEL_NANS, 2) == {"count": 2}
    assert inj.fires(SITE_PANEL_NANS, 2) is None  # consumed
    assert inj.pending() == 0
    assert [e.payload for e in inj.fired_at(SITE_PANEL_NANS)] == [
        {"count": 1},
        {"count": 2},
    ]


def test_injector_rejects_unknown_site_and_negative_when():
    inj = FaultInjector()
    with pytest.raises(ValueError, match="unknown fault site"):
        inj.schedule("not-a-site", 0)
    with pytest.raises(ValueError, match="when"):
        inj.schedule(SITE_PANEL_NANS, -1)


def test_injector_rng_is_seeded():
    a = FaultInjector(seed=7).rng.integers(0, 1 << 30, size=8)
    b = FaultInjector(seed=7).rng.integers(0, 1 << 30, size=8)
    assert np.array_equal(a, b)


def test_poison_panel_columns_and_limit():
    panel = _panel(0, 8, 6)
    poisoned, cols = poison_panel(panel, columns=[1, 4])
    assert cols == (1, 4)
    assert not bool(jnp.isfinite(poisoned[:, 1]).any())
    assert not bool(jnp.isfinite(poisoned[:, 4]).any())
    for j in (0, 2, 3, 5):  # untouched columns are bit-identical
        assert np.array_equal(poisoned[:, j], panel[:, j])
    # limit keeps random choice inside the real (non-pad) columns
    rng = np.random.default_rng(0)
    _, cols = poison_panel(panel, count=3, limit=4, rng=rng)
    assert len(cols) == 3 and all(c < 4 for c in cols)
    with pytest.raises(ValueError, match="out of range"):
        poison_panel(panel, columns=[5], limit=4)
    with pytest.raises(ValueError, match="mode"):
        poison_panel(panel, mode="zero")


# ---------------------------------------------------------------------
# engine: quarantine / retry / graceful failure / eviction
# ---------------------------------------------------------------------


def test_engine_quarantines_only_poisoned_requests():
    m, k = 32, 6
    ws, bs = _bsr_stack(1, 3, m)
    clean = SparseDNNEngine(ws, bs, batch_align=8)
    ref, _ = clean.infer(_panel(1, m, k))

    inj = FaultInjector(seed=0)
    inj.schedule(SITE_PANEL_NANS, 0, columns=[1, 4])
    eng = SparseDNNEngine(ws, bs, batch_align=8, fault_injector=inj)
    out, stats = eng.infer(_panel(1, m, k))
    assert stats["failed"] is False
    # exactly the poisoned requests fail; NaN propagates through the
    # ReLU stack column-separably, so the rest of the panel is unharmed
    assert stats["quarantined_request_ids"] == [1, 4]
    for j in (0, 2, 3, 5):
        assert np.array_equal(out[:, j], ref[:, j])
    assert not bool(jnp.isfinite(out[:, 1]).any())


def test_engine_retries_transient_then_succeeds():
    m = 32
    ws, bs = _bsr_stack(2, 2, m)
    inj = FaultInjector()
    inj.schedule(SITE_STEP_TRANSIENT, 0, failures=2)
    eng = SparseDNNEngine(
        ws, bs, batch_align=8, fault_injector=inj, max_step_retries=2
    )
    clean = SparseDNNEngine(ws, bs, batch_align=8)
    out, stats = eng.infer(_panel(3, m, 4))
    ref, _ = clean.infer(_panel(3, m, 4))
    assert stats["failed"] is False
    assert stats["retries"] == 2  # two injected failures, then success
    assert np.array_equal(out, ref)


def test_engine_fails_gracefully_after_retry_exhaustion():
    m = 32
    ws, bs = _bsr_stack(2, 2, m)
    inj = FaultInjector()
    inj.schedule(SITE_STEP_TRANSIENT, 0, failures=10)  # > retries
    eng = SparseDNNEngine(
        ws, bs, batch_align=8, fault_injector=inj, max_step_retries=2
    )
    out, stats = eng.infer(_panel(4, m, 4))  # must NOT raise
    assert out is None
    assert stats["failed"] is True
    assert stats["retries"] == 2
    assert stats["request_ids"] == [0, 1, 2, 3]  # the lost requests
    assert "TransientFault" in stats["error"]
    # the engine survives: the next panel serves normally
    out2, stats2 = eng.infer(_panel(5, m, 4))
    assert stats2["failed"] is False and bool(jnp.isfinite(out2).all())


def test_engine_cache_eviction_storm_recompiles():
    m = 32
    ws, bs = _bsr_stack(3, 2, m)
    inj = FaultInjector()
    inj.schedule(SITE_CACHE_EVICTION, 2)
    eng = SparseDNNEngine(ws, bs, batch_align=8, fault_injector=inj)
    _, s0 = eng.infer(_panel(0, m, 4))
    _, s1 = eng.infer(_panel(1, m, 4))
    assert s0["plan"]["cache_hit"] is False  # first build
    assert s1["plan"]["cache_hit"] is True  # warm
    _, s2 = eng.infer(_panel(2, m, 4))  # eviction storm fires here
    _, s3 = eng.infer(_panel(3, m, 4))
    assert s2["plan"]["cache_hit"] is False  # forced recompile
    assert s3["plan"]["cache_hit"] is True  # warm again


# ---------------------------------------------------------------------
# degradation ladder
# ---------------------------------------------------------------------


def test_restore_reverses_demotion_and_logs_up_transition():
    """Restore semantics: after ``restore()`` the next panel re-plans at
    the restored level, the event history records the up-transition
    (healthy=True) with the operator's reason/step, and restoring an
    already-healthy level is a silent no-op."""
    from repro.launch.mesh import make_row_blocks_mesh

    m = 32
    ws, bs = _csr_stack(9, 2, m)
    mesh = make_row_blocks_mesh(1)
    inj = FaultInjector()
    inj.schedule(SITE_PLAN_COMPILE, 0)
    eng = SparseDNNEngine(ws, bs, batch_align=8, mesh=mesh, fault_injector=inj)
    assert eng.ladder.preferred_level == LEVEL_SHARDED
    _, s0 = eng.infer(_panel(20, m, 4))
    assert s0["failed"] is False
    assert s0["plan"]["level"] == LEVEL_LAYERED  # demoted by the fault
    assert s0["plan"]["degraded"] is True

    eng.ladder.restore(LEVEL_SHARDED, reason="node re-slotted", step=7)
    # the history records the full round trip: down, then up
    assert [(e.level, e.healthy) for e in eng.ladder.events] == [
        (LEVEL_SHARDED, False),
        (LEVEL_SHARDED, True),
    ]
    up = eng.ladder.events[-1]
    assert up.reason == "node re-slotted" and up.step == 7
    assert eng.ladder.is_healthy(LEVEL_SHARDED)
    assert not eng.ladder.degraded
    # idempotent: restoring a healthy level appends NO duplicate event
    eng.ladder.restore(LEVEL_SHARDED)
    assert len(eng.ladder.events) == 2
    # the floor has no health state to restore
    with pytest.raises(ValueError, match="health"):
        eng.ladder.restore(LEVEL_LAYERED)

    # the next panel re-plans at the restored level — and still matches
    # a never-degraded engine bit for bit
    clean = SparseDNNEngine(ws, bs, batch_align=8, mesh=mesh)
    p = _panel(21, m, 4)
    out, s1 = eng.infer(p)
    ref, _ = clean.infer(p)
    assert s1["plan"]["level"] == LEVEL_SHARDED
    assert s1["plan"]["degraded"] is False
    assert np.array_equal(out, ref)
    # the serve-stats surface sees the same round trip
    d = eng.ladder.describe()
    assert d["current"] == d["preferred"] == LEVEL_SHARDED
    assert [e["healthy"] for e in d["events"]] == [False, True]


def test_shard_failure_degrades_to_single_device_same_results():
    from repro.launch.mesh import make_row_blocks_mesh

    m = 32
    ws, bs = _csr_stack(5, 2, m)
    mesh = make_row_blocks_mesh(1)
    single = SparseDNNEngine(ws, bs, batch_align=8)
    inj = FaultInjector()
    inj.schedule(SITE_SHARD_FAILURE, 1, reason="node 3 lost")
    eng = SparseDNNEngine(ws, bs, batch_align=8, mesh=mesh, fault_injector=inj)

    p0, p1 = _panel(9, m, 4), _panel(10, m, 4)
    _, s0 = eng.infer(p0)
    assert s0["plan"]["level"] == LEVEL_SHARDED  # healthy mesh first
    out1, s1 = eng.infer(p1)  # shard dies at this dispatch
    ref1, sref = single.infer(p1)
    # the in-flight panel is NOT dropped: same fingerprint re-planned on
    # a single device, identical results to a healthy single-device run
    assert s1["failed"] is False
    assert s1["plan"]["level"] == sref["plan"]["level"]
    assert s1["plan"]["degraded"] is True
    assert np.array_equal(out1, ref1)
    assert eng.ladder.degraded
    assert [e.level for e in eng.ladder.events] == [LEVEL_SHARDED]


# ---------------------------------------------------------------------
# batcher: backpressure / shedding / stragglers / goodput
# ---------------------------------------------------------------------


def test_bounded_queue_backpressure():
    from repro.serve import RequestQueue

    q = RequestQueue(max_pending=2)
    q.submit(_col(0, 8), now=0)
    q.submit(_col(1, 8), now=0)
    with pytest.raises(QueueFull):
        q.submit(_col(2, 8), now=0)
    m = 32
    ws, bs = _bsr_stack(6, 2, m)
    b = ContinuousBatcher(
        SparseDNNEngine(ws, bs, batch_align=4),
        batch_size=4,
        max_pending=2,
    )
    assert b.submit(_col(0, m)) is not None
    assert b.submit(_col(1, m)) is not None
    assert b.submit(_col(2, m)) is None  # rejected, not raised
    b.drain()
    s = b.stats()
    assert s.faults.offered == 3
    assert s.faults.rejected == 1
    assert s.requests == 2
    assert s.goodput == pytest.approx(2 / 3)


def test_batcher_straggler_tick_and_failed_step_accounting():
    m = 32
    ws, bs = _bsr_stack(7, 2, m)
    inj = FaultInjector()
    inj.schedule(SITE_STRAGGLER, 0, seconds=0.0)
    inj.schedule(SITE_STEP_TRANSIENT, 0, failures=10)  # kill panel 0
    eng = SparseDNNEngine(
        ws, bs, batch_align=4, fault_injector=inj, max_step_retries=1
    )
    b = ContinuousBatcher(eng, batch_size=4, fault_injector=inj)
    r0 = b.submit(_col(0, m))
    b.step()  # straggles, then the panel dies after retries
    r1 = b.submit(_col(1, m))
    b.drain()
    s = b.stats()
    assert s.faults.straggler_ticks == 1
    assert s.faults.failed_steps == 1
    assert s.faults.failed == 1
    assert s.faults.retried_steps == 1
    assert "step failed" in b.failures[r0]
    assert r1 in s.latencies  # the stream survived the dead panel
    assert s.goodput == pytest.approx(1 / 2)


def test_injected_trace_completes_with_goodput():
    """End-to-end: a trace with NaN panels, a transient failure, an
    eviction storm, and a straggler completes without raising and the
    quarantine fails only the poisoned requests."""
    m = 32
    ws, bs = _bsr_stack(8, 3, m)
    inj = FaultInjector(seed=1)
    inj.schedule(SITE_PANEL_NANS, 1, count=1)
    inj.schedule(SITE_STEP_TRANSIENT, 2, failures=1)  # retried, no loss
    inj.schedule(SITE_CACHE_EVICTION, 3)
    inj.schedule(SITE_STRAGGLER, 2, seconds=0.0)
    eng = SparseDNNEngine(ws, bs, batch_align=4, fault_injector=inj)
    b = ContinuousBatcher(eng, batch_size=4, fault_injector=inj)
    n = 24
    for i in range(n):
        b.submit(_col(100 + i, m))
        if i % 2:
            b.step()
    b.drain()
    s = b.stats()
    assert s.faults.quarantined == 1
    assert s.faults.retried_steps == 1
    assert s.faults.straggler_ticks == 1
    assert s.faults.failed == 0
    assert s.requests == n - 1  # everything but the quarantined one
    assert s.goodput == pytest.approx((n - 1) / n)
    assert inj.pending() == 0  # every armed fault actually fired
    quarantined = [r for r, why in b.failures.items() if "quarantine" in why]
    assert len(quarantined) == 1
    for rid, lat in s.latencies.items():
        assert bool(jnp.isfinite(b.result(rid)).all())
