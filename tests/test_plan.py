"""Compile-once execution plans (``repro.plan``): cache keying,
eviction, route decisions, plan-backed forward equivalence, the cached
block-CSR transpose (a multi-step train loop sorts the topology exactly
once), and the serving integration (engine plan stats, width-class
quantization, per-class recompile counts)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan as P
from repro.core import dnn
from repro.serve import ContinuousBatcher, SparseDNNEngine
from repro.sparse import (
    BlockCSRMatrix,
    BlockSparseMatrix,
    reset_transpose_sort_count,
    transpose_sort_count,
)


def _stack(key, L, m, bpr=2, block=16):
    ks = jax.random.split(key, L)
    ws = [
        BlockSparseMatrix.random(k, (m, m), (block, block), blocks_per_row=bpr)
        for k in ks
    ]
    bs = [jnp.zeros((m,), jnp.float32) for _ in range(L)]
    return ws, bs


def _skewed_ell(seed, m=64, block=16):
    """An ELL weight whose pad waste crosses the threshold (one heavy
    row, the rest near-empty) → preferred_layout == 'bcsr'."""
    nrb = m // block
    dense = np.zeros((m, m), np.float32)
    dense[:block, :] = 1.0  # first block-row full
    dense[block : 2 * block, :block] = 1.0  # second has one block
    return BlockSparseMatrix.from_dense(jnp.asarray(dense), (block, block))


# ---------------------------------------------------------------------
# fingerprint + width classes
# ---------------------------------------------------------------------


def test_fingerprint_is_topology_only():
    # bpr=1 over a 4x4 block grid → the stored-block pattern genuinely
    # varies with the seed (full-occupancy stacks all look alike)
    ws, _ = _stack(jax.random.PRNGKey(0), 2, 64, bpr=1)
    same_pattern = [w.map_blocks(lambda x: x * 2.0) for w in ws]
    other, _ = _stack(jax.random.PRNGKey(9), 2, 64, bpr=1)
    assert not np.array_equal(
        np.asarray(ws[0].col_idx), np.asarray(other[0].col_idx)
    )
    fp = P.topology_fingerprint(ws)
    assert P.topology_fingerprint(same_pattern) == fp  # values don't key
    assert P.topology_fingerprint(other) != fp  # pattern does
    # layout class is part of the topology
    csr = [BlockCSRMatrix.from_bsr(w) for w in ws]
    assert P.topology_fingerprint(csr) != fp


def test_quantize_width():
    classes = (8, 16, 32)
    assert P.quantize_width(1, classes) == 8
    assert P.quantize_width(8, classes) == 8
    assert P.quantize_width(9, classes) == 16
    assert P.quantize_width(32, classes) == 32
    assert P.quantize_width(33, classes) == 64  # beyond top: multiples
    assert P.quantize_width(17, None) == 17  # no classes → identity


# ---------------------------------------------------------------------
# cache keying + eviction (the satellite's contract)
# ---------------------------------------------------------------------


def test_cache_same_topology_same_width_hits():
    ws, bs = _stack(jax.random.PRNGKey(1), 2, 32)
    cache = P.PlanCache(max_size=4)
    p1 = cache.get(ws, bs, 16)
    p2 = cache.get(ws, bs, 16)
    assert p1 is p2
    assert cache.stats()["hits"] == 1 and cache.stats()["builds"] == 1


def test_cache_distinct_plans_per_key_axis():
    ws, bs = _stack(jax.random.PRNGKey(2), 2, 64, bpr=1)
    other, _ = _stack(jax.random.PRNGKey(3), 2, 64, bpr=1)
    assert P.topology_fingerprint(ws) != P.topology_fingerprint(other)
    cache = P.PlanCache(max_size=8)
    base = cache.get(ws, bs, 16)
    # changed block pattern → distinct plan
    assert cache.get(other, bs, 16) is not base
    # changed width class → distinct plan
    assert cache.get(ws, bs, 32) is not base
    # toggled differentiable → distinct plan
    assert cache.get(ws, bs, 16, differentiable=True) is not base
    assert cache.stats()["builds"] == 4
    # and each key still hits on repeat
    assert cache.get(ws, bs, 16) is base


def test_cache_eviction_respects_max_size():
    ws, bs = _stack(jax.random.PRNGKey(4), 2, 32)
    cache = P.PlanCache(max_size=2)
    p8 = cache.get(ws, bs, 8)
    cache.get(ws, bs, 16)
    cache.get(ws, bs, 32)  # evicts the LRU entry (width 8)
    assert len(cache) == 2
    assert cache.stats()["evictions"] == 1
    assert cache.get(ws, bs, 8) is not p8  # rebuilt after eviction
    assert cache.stats()["builds"] == 4


def test_cache_rejects_stale_bound_values():
    """Same topology but different value arrays must NOT reuse a plan
    whose executable binds the old values."""
    ws, bs = _stack(jax.random.PRNGKey(5), 2, 32)
    rescaled = [w.map_blocks(lambda x: x * 3.0) for w in ws]
    cache = P.PlanCache(max_size=4)
    p1 = cache.get(ws, bs, 8)
    p2 = cache.get(rescaled, bs, 8)
    assert p1 is not p2
    y0 = jax.random.uniform(jax.random.PRNGKey(6), (32, 4))
    np.testing.assert_allclose(
        np.asarray(p2.forward(y0)),
        np.asarray(dnn.dnn_forward(rescaled, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


# ---------------------------------------------------------------------
# route decisions + plan-backed forward equivalence
# ---------------------------------------------------------------------


def test_route_fused_for_homogeneous_square_stack():
    ws, bs = _stack(jax.random.PRNGKey(7), 3, 64)
    plan = P.build_plan(ws, bs, 8)
    assert plan.route == P.ROUTE_FUSED
    assert plan.pallas_calls == 1
    y0 = jax.random.uniform(jax.random.PRNGKey(8), (64, 5))
    np.testing.assert_allclose(
        np.asarray(plan.forward(y0)),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_route_layered_for_mixed_layout_and_grid_steps():
    ws, bs = _stack(jax.random.PRNGKey(10), 2, 64)
    mixed = [BlockCSRMatrix.from_bsr(ws[0]), ws[1]]
    plan = P.build_plan(mixed, bs, 8)
    assert plan.route == P.ROUTE_LAYERED
    assert plan.layouts == ("bcsr", "ell")
    assert plan.pallas_calls == 2
    assert plan.grid_steps == dnn.dnn_grid_steps(mixed, 8)
    y0 = jax.random.uniform(jax.random.PRNGKey(11), (64, 8))
    np.testing.assert_allclose(
        np.asarray(plan.forward(y0)),
        np.asarray(dnn.dnn_forward(mixed, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_route_xla_for_all_dense_differentiable():
    m = 32
    ws = [jax.random.normal(jax.random.PRNGKey(12), (m, m)) * 0.1]
    bs = [jnp.zeros((m,))]
    plan = P.build_plan(ws, bs, 8, differentiable=True)
    assert plan.route == P.ROUTE_XLA
    assert plan.pallas_calls == 0


def test_relayout_applies_waste_heuristic_to_inference_plans():
    w = _skewed_ell(0)
    assert P.preferred_layout(w) == "bcsr"
    bs = [jnp.zeros((64,), jnp.float32)]
    # the fused route would win on this square stack — force layered to
    # exercise the per-layer waste heuristic
    plan = P.build_plan([w], bs, 8, use_resident=False)
    assert plan.layers[0].source_layout == "ell"
    assert plan.layers[0].layout == "bcsr"  # the lifted heuristic fired
    y0 = jax.random.uniform(jax.random.PRNGKey(13), (64, 8))
    np.testing.assert_allclose(
        np.asarray(plan.forward(y0)),
        np.asarray(dnn.dnn_forward([w], bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )
    # differentiable plans must keep the caller's layout (cotangent
    # structure mirrors the primal) — relayout is refused
    dplan = P.build_plan([w], bs, 8, differentiable=True)
    assert dplan.layers[0].layout == "ell"
    with pytest.raises(ValueError, match="relayout"):
        P.build_plan([w], bs, 8, differentiable=True, relayout=True)


def test_plan_forward_pads_to_width_class_and_rejects_overflow():
    ws, bs = _stack(jax.random.PRNGKey(14), 2, 32)
    plan = P.build_plan(ws, bs, 16)
    y0 = jax.random.uniform(jax.random.PRNGKey(15), (32, 3))
    out = plan.forward(y0)  # 3 ≤ 16: padded internally, sliced back
    assert out.shape == (32, 3)
    assert plan.compile_count == 1
    plan.forward(jax.random.uniform(jax.random.PRNGKey(16), (32, 9)))
    assert plan.compile_count == 1  # same class → same executable
    with pytest.raises(ValueError, match="width"):
        plan.forward(jnp.zeros((32, 17)))


def test_repeated_layer_objects_are_relaid_once():
    """RadiX-net repeats its phase matrices through the stack: the plan
    re-lays each distinct weight object once and reuses it, so the device
    holds one execution copy per object, not one per layer."""
    from repro.data import radixnet as rx

    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(256, 6))
    assert len({id(w) for w in ws}) == 2
    plan = P.build_plan(ws, bs, 16, use_resident=False)
    assert plan.layouts == ("component",) * 6
    assert len({id(w) for w in plan.weights}) == 2
    assert all(plan.weights[i] is plan.weights[i % 2] for i in range(6))


def test_the_executable_takes_each_distinct_weight_once():
    """The layered executable's arguments hold each distinct weight, bias
    and gather once, and every layer reads its own by index: six layers
    of two phases lower to two weight parameters, and the answers equal
    a stack of six distinct copies of the same weights."""
    from repro.data import radixnet as rx

    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(256, 6))
    plan = P.build_plan(ws, bs, 16, use_resident=False)
    assert plan.weight_args == 2
    weights, biases, gathers = plan._bound()
    assert [w is plan.weights[i] for i, w in enumerate(weights)] == [True, True]
    assert len(biases) == 2  # the bias in each phase's row order
    copies = [jax.tree.map(jnp.copy, w) for w in ws]  # six distinct objects
    apart = P.build_plan(copies, bs, 16, use_resident=False)
    assert apart.weight_args == 6

    def params(p):  # the compiled program's parameters
        text = p.lower().as_text()
        head = text[text.index("func.func public @main(") :].split("\n", 1)[0]
        return head.count("%arg")

    per_weight = len(jax.tree.leaves(weights[0]))
    assert params(plan) == len(jax.tree.leaves(plan._bound())) + 1
    assert params(apart) - params(plan) == 4 * per_weight + 4  # weights, biases
    y0 = jnp.asarray(rx.radixnet_input_panel(256, 16, density=0.3, seed=2))
    np.testing.assert_array_equal(
        np.asarray(plan.forward(y0)), np.asarray(apart.forward(y0))
    )
    fused = P.build_plan(ws, bs, 16)
    assert fused.is_fused_route and fused.weight_args == 1


def test_vmem_boundary_tips_fused_into_tiled_exactly():
    """Regression for the route boundary: the last m whose activation
    panel exactly fills ``VMEM_SOFT_LIMIT_BYTES`` still takes the
    resident fused route; ONE block-row more must tip into fused-tiled
    (never layered). Asserted through the plan layer's decision tree,
    not the kernel. The panel bill counts the six (m, block_n) stripes
    the v5e compiler allocates, so the boundary sits at m = 4096 (at
    6144, where four stripes put it, the compiler refused the resident
    kernel: 18 MiB scoped VMEM against a 16 MiB limit)."""
    from repro.kernels.fused_mlp import (
        VMEM_SOFT_LIMIT_BYTES,
        fused_mlp_vmem_bytes,
    )

    block = 16
    bytes_per_row = fused_mlp_vmem_bytes(1)
    m_res = VMEM_SOFT_LIMIT_BYTES // bytes_per_row  # last resident m
    assert fused_mlp_vmem_bytes(m_res) == VMEM_SOFT_LIMIT_BYTES
    assert m_res % block == 0

    at, bs_at = _stack(jax.random.PRNGKey(40), 2, m_res, block=block)
    over, bs_over = _stack(
        jax.random.PRNGKey(41), 2, m_res + block, block=block
    )
    # the three-way route call is exact at the boundary
    assert P.fused_route(at) == P.ROUTE_FUSED
    assert P.fused_route(over) == P.ROUTE_FUSED_TILED
    # ...and build_plan agrees: both stay single-pallas_call plans
    plan_at = P.build_plan(at, bs_at, 8)
    plan_over = P.build_plan(over, bs_over, 8)
    assert plan_at.route == P.ROUTE_FUSED
    assert plan_over.route == P.ROUTE_FUSED_TILED
    assert plan_over.route != P.ROUTE_LAYERED
    assert plan_at.pallas_calls == plan_over.pallas_calls == 1
    # the over-budget stack still honours the engine's resident knob
    # (fused family), and use_resident=False forces layered as usual
    assert (
        P.build_plan(over, bs_over, 8, use_resident=True).route
        == P.ROUTE_FUSED_TILED
    )
    assert (
        P.build_plan(over, bs_over, 8, use_resident=False).route
        == P.ROUTE_LAYERED
    )


def test_use_resident_tristate_matches_engine_contract():
    ws, bs = _stack(jax.random.PRNGKey(17), 2, 64)
    assert P.build_plan(ws, bs, 8, use_resident=True).route == P.ROUTE_FUSED
    assert P.build_plan(ws, bs, 8, use_resident=False).route == P.ROUTE_LAYERED
    with pytest.raises(ValueError, match="not eligible"):
        P.build_plan(
            [BlockCSRMatrix.from_bsr(ws[0])], bs[:1], 8, use_resident=True
        )
    with pytest.raises(ValueError, match="VJP|eligible"):
        P.build_plan(ws, bs, 8, differentiable=True, use_resident=True)


# ---------------------------------------------------------------------
# the cached transpose: one sort per topology, ever
# ---------------------------------------------------------------------


def test_train_loop_sorts_topology_exactly_once():
    """10 jitted train steps over an ELL+CSR stack: the CSR topology is
    argsorted exactly once (at plan build); the step's jaxpr contains no
    sort at all, while the legacy (plan-less) step still sorts."""
    from repro.train.optimizer import sgd
    from repro.train.sparse import (
        init_sparse_mlp_state,
        make_sparse_train_step,
    )

    m, n = 32, 8
    ws, bs = _stack(jax.random.PRNGKey(18), 2, m)
    ws = [ws[0], BlockCSRMatrix.from_bsr(ws[1])]
    y0 = jax.random.uniform(jax.random.PRNGKey(19), (m, n))
    batch = {"y0": y0, "targets": y0 * 0.5}
    opt = sgd(0.1, momentum=0.0)
    state = init_sparse_mlp_state(ws, bs, opt)

    legacy = make_sparse_train_step(opt, use_kernel=True)
    assert " sort" in str(jax.make_jaxpr(legacy)(state, batch))

    reset_transpose_sort_count()
    plan = P.build_plan(ws, bs, n, differentiable=True)
    assert transpose_sort_count() == 1  # one CSR layer → one sort
    planned = make_sparse_train_step(opt, use_kernel=True, plan=plan)
    assert " sort" not in str(jax.make_jaxpr(planned)(state, batch))

    step = jax.jit(planned)
    losses = []
    for _ in range(10):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert transpose_sort_count() == 1  # 10 steps added ZERO sorts
    assert losses[-1] < losses[0]


def test_cache_shares_topology_artifacts_across_width_classes():
    """Plans for new width classes donate from an existing plan: the
    topology is sorted once no matter how many classes serve it, and
    fused plans share one stacked weight copy."""
    ws, bs = _stack(jax.random.PRNGKey(34), 2, 32)
    mixed = [BlockCSRMatrix.from_bsr(ws[0]), ws[1]]
    cache = P.PlanCache(max_size=8)
    reset_transpose_sort_count()
    p8 = cache.get(mixed, bs, 8, differentiable=True)
    p16 = cache.get(mixed, bs, 16, differentiable=True)
    assert transpose_sort_count() == 1  # second width class: no re-sort
    assert p16.layers[0].transpose_plan is p8.layers[0].transpose_plan
    assert p16.grid_steps == dnn.dnn_grid_steps(mixed, 16)  # width-local
    f8 = cache.get(ws, bs, 8)
    f16 = cache.get(ws, bs, 16)
    assert f8.route == f16.route == P.ROUTE_FUSED
    assert f16._stacked is f8._stacked  # one device copy per topology
    y0 = jax.random.uniform(jax.random.PRNGKey(35), (32, 10))
    np.testing.assert_allclose(
        np.asarray(f16.forward(y0)),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_dnn_forward_resident_stays_differentiable_on_fallback():
    """Regression: grad through dnn_forward_resident on an ineligible
    stack with a dense layer must keep the legacy XLA-differentiable
    fallback (the plan path would route the dense layer to the VJP-less
    Pallas kernel)."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(36), 1, m)
    mixed = [ws[0], jax.random.normal(jax.random.PRNGKey(37), (m, m)) * 0.1]
    bs = bs + [jnp.zeros((m,))]
    y0 = jax.random.uniform(jax.random.PRNGKey(38), (m, 4))
    g = jax.grad(
        lambda y: jnp.sum(dnn.dnn_forward_resident(mixed, bs, y))
    )(y0)
    assert g.shape == y0.shape
    assert float(jnp.abs(g).max()) > 0.0


def test_planned_grads_match_legacy():
    m, n = 32, 8
    ws, bs = _stack(jax.random.PRNGKey(20), 2, m)
    ws = [BlockCSRMatrix.from_bsr(ws[0]), ws[1]]
    y0 = jax.random.uniform(jax.random.PRNGKey(21), (m, n))
    targets = jax.random.uniform(jax.random.PRNGKey(22), (m, n))
    plan = P.build_plan(ws, bs, n, differentiable=True)
    l1, (dw1, db1) = dnn.dnn_value_and_grad(ws, bs, y0, targets)
    l2, (dw2, db2) = dnn.dnn_value_and_grad(ws, bs, y0, targets, plan=plan)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(dw1[0].values), np.asarray(dw2[0].values), rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(dw1[1].blocks), np.asarray(dw2[1].blocks), rtol=1e-5
    )
    np.testing.assert_allclose(np.asarray(db1[0]), np.asarray(db2[0]), rtol=1e-5)


def test_forward_trainable_requires_matching_plan():
    ws, bs = _stack(jax.random.PRNGKey(23), 2, 32)
    inference_plan = P.build_plan(ws, bs, 8)
    with pytest.raises(ValueError, match="differentiable"):
        dnn.dnn_forward_trainable(
            ws, bs, jnp.zeros((32, 8)), plan=inference_plan
        )
    short = P.build_plan(ws[:1], bs[:1], 8, differentiable=True)
    with pytest.raises(ValueError, match="layers"):
        dnn.dnn_forward_trainable(ws, bs, jnp.zeros((32, 8)), plan=short)


# ---------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------


def test_engine_steps_share_one_plan_per_width_class():
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(24), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    _, s1 = eng.infer(jax.random.uniform(jax.random.PRNGKey(25), (m, 5)))
    _, s2 = eng.infer(jax.random.uniform(jax.random.PRNGKey(26), (m, 7)))
    assert s1["plan"]["width_class"] == s2["plan"]["width_class"] == 8
    assert s1["plan"]["cache_hit"] is False  # first panel built the plan
    assert s2["plan"]["cache_hit"] is True  # second reused it
    assert s2["plan"]["compiles"] == 1  # ... without recompiling
    assert eng.plan_cache.stats()["builds"] == 1
    _, s3 = eng.infer(jax.random.uniform(jax.random.PRNGKey(27), (m, 9)))
    assert s3["plan"]["width_class"] == 16  # new class → new plan
    assert eng.plan_cache.stats()["builds"] == 2


def test_engine_pad_to_quantizes_panel():
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(28), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    eng.submit(jax.random.uniform(jax.random.PRNGKey(29), (m, 3)))
    out, stats = eng.step(pad_to=24)
    assert stats["padded_batch"] == 24 and stats["pad_slots"] == 21
    assert stats["grid_steps"] == dnn.dnn_grid_steps(ws, 24)
    assert out.shape == (m, 3)
    with pytest.raises(ValueError):
        eng.step(pad_to=0)


def test_batcher_width_classes_reuse_compiled_plans():
    """The satellite knob: quantized panels land on a handful of width
    classes; the plan cache compiles once per class and ServeStats
    reports the per-class recompile counts."""
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(30), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=8)
    b = ContinuousBatcher(
        eng, batch_size=16, min_fill=0.0, width_classes=(8, 16)
    )
    cols = {}
    for i in range(23):  # varying occupancies across ticks
        for j in range(1 + (i * 5) % 7):
            col = jax.random.uniform(jax.random.PRNGKey(100 + 10 * i + j), (m,))
            cols[b.submit(col)] = col
        b.step(force=True)
    b.drain()
    stats = b.stats()
    assert stats.requests == len(cols)
    # every panel landed on a declared class
    assert {s.width_class for s in stats.steps} <= {8, 16}
    # one compile per class touched, everything else reused
    assert sum(stats.plan_recompiles_by_class.values()) == len(
        stats.plan_recompiles_by_class
    )
    assert eng.plan_cache.stats()["builds"] == len(
        stats.plan_recompiles_by_class
    )
    assert stats.plan_cache_hit_rate >= 0.8
    # numbers unchanged by quantization
    for rid, col in cols.items():
        np.testing.assert_allclose(
            np.asarray(b.result(rid)),
            np.asarray(dnn.dnn_forward(ws, bs, col[:, None], fused=True)[:, 0]),
            rtol=1e-5,
            atol=1e-5,
        )


def test_batcher_width_classes_validation():
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(31), 2, m)
    with pytest.raises(ValueError, match="width class"):
        ContinuousBatcher(
            SparseDNNEngine(ws, bs, batch_align=8),
            batch_size=32,
            width_classes=(8, 16),  # largest class < batch_size
        )
    with pytest.raises(ValueError, match="positive"):
        ContinuousBatcher(
            SparseDNNEngine(ws, bs, batch_align=8),
            batch_size=4,
            width_classes=(0, 8),
        )


def test_differentiable_engine_grad_flows_through_plan():
    m = 32
    ws, bs = _stack(jax.random.PRNGKey(32), 2, m)
    eng = SparseDNNEngine(ws, bs, batch_align=4, differentiable=True)
    y0 = jax.random.uniform(jax.random.PRNGKey(33), (m, 4))
    g = jax.grad(lambda y: jnp.sum(eng.infer(y)[0]))(y0)
    assert g.shape == y0.shape
    assert float(jnp.abs(g).max()) > 0.0
