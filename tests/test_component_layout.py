"""The component layout (``repro.plan.components``): detection on the
RadiX-net phases, the three engagement conditions, the layered plan's
row gathers against the numpy reference, where it stays off, and what
a donor plan shares."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan as P
from repro.data import radixnet as rx
from repro.plan.components import component_layout
from repro.sparse import BlockCSRMatrix, BlockSparseMatrix
from repro.tune import TunedConfig


@functools.cache
def _phases(neurons):
    ws, bs = rx.radixnet_weights(rx.RadixNetSpec(neurons, rx.num_phases(neurons)))
    return ws, bs


def _stack(neurons, layers):
    ws, bs = _phases(neurons)
    return [ws[i % len(ws)] for i in range(layers)], [bs[0]] * layers


def _block_diagonal(sizes, seed=0, m=None):
    """A weight whose components are dense blocks of the given
    ``(r, c)`` sizes, rows and columns shuffled, as 16x16 ELL."""
    rng = np.random.default_rng(seed)
    m = m or sum(r for r, _ in sizes)
    k = sum(c for _, c in sizes)
    dense = np.zeros((m, k), np.float32)
    i = j = 0
    for r, c in sizes:
        dense[i : i + r, j : j + c] = rng.uniform(0.5, 2.0, (r, c))
        i, j = i + r, j + c
    dense = dense[rng.permutation(m)][:, rng.permutation(k)]
    return BlockSparseMatrix.from_dense(jnp.asarray(dense), (16, 16)), dense


@pytest.mark.parametrize(
    "neurons,phase",
    [(1024, 0), (1024, 1), (16384, 0), (16384, 1), (16384, 2),
     (65536, 0), (65536, 1), (65536, 2), (65536, 3)],
    ids=["1024-p0", "1024-p1", "16384-p0", "16384-p1", "16384-p2",
         "65536-p0", "65536-p1", "65536-p2", "65536-p3"],
)
def test_each_radixnet_phase_splits_into_complete_32x32_components(neurons, phase):
    w = _phases(neurons)[0][phase]
    cl = component_layout(w)
    assert cl is not None
    assert cl.shape == (32, 32)
    assert cl.n_components == neurons // 32
    group = cl.weight.block_shape[1]  # components per block-row
    assert cl.weight.block_shape == (group * 32, group)
    assert cl.weight.total_blocks == cl.n_components // group * 32
    for order in (cl.rows, cl.cols):
        assert np.array_equal(np.sort(order), np.arange(neurons))
    # each row meets exactly one edge (1/16) in each of its 32 blocks
    values = np.asarray(cl.weight.values)
    assert np.all((values != 0).sum(axis=2) == 1)
    assert np.all(values[values != 0] == rx.WEIGHT_VALUE)
    conn = rx.radixnet_connectivity(neurons, phase)
    # a component's rows share its columns, which its blocks take in
    # ascending order
    for g in (0, cl.n_components // 2, cl.n_components - 1):
        b, i = divmod(g, group)
        cols = cl.cols[b * 32 * group : (b + 1) * 32 * group]
        cols = cols.reshape(32, group)[:, i]
        for r in cl.rows[g * 32 : (g + 1) * 32]:
            assert np.array_equal(np.sort(conn[r]), cols)


@pytest.mark.parametrize(
    "sizes", [[(16, 24)] * 8, [(4, 4)] * 16], ids=["16x24", "4x4"]
)
def test_relaid_weight_is_the_layer_permuted(sizes):
    w, dense = _block_diagonal(sizes, seed=3)
    cl = component_layout(w)
    assert cl is not None and cl.shape == sizes[0]
    np.testing.assert_array_equal(
        np.asarray(cl.weight.to_dense()), dense[np.ix_(cl.rows, cl.cols)]
    )
    # a block-CSR copy of the same layer gives the same layout
    again = component_layout(BlockCSRMatrix.from_bsr(w))
    np.testing.assert_array_equal(again.rows, cl.rows)
    np.testing.assert_array_equal(again.cols, cl.cols)


def _one_edge_removed():
    w = _phases(1024)[0][1]
    blocks = np.array(w.blocks)
    rb, slot, i, j = (a[0] for a in np.nonzero(blocks))
    blocks[rb, slot, i, j] = 0.0
    return BlockSparseMatrix(
        jnp.asarray(blocks), w.col_idx, w.block_mask, w.shape, w.block_shape
    )


@pytest.mark.parametrize(
    "make",
    [
        lambda: _one_edge_removed(),  # one component no longer complete
        lambda: _block_diagonal([(16, 16), (32, 32)])[0],  # two shapes
        lambda: _block_diagonal([(16, 16)] * 4)[0],  # 4 components, not 8
        lambda: _block_diagonal([(256, 256)] * 8)[0],  # rows past 128
        lambda: _block_diagonal([(16, 16)] * 3, m=64)[0],  # empty rows
        lambda: BlockCSRMatrix.random_skewed(
            0, (256, 256), (16, 16), 96, skew=0.6
        ),
        lambda: jnp.ones((64, 64), jnp.float32),  # dense weights stay dense
    ],
    ids=["edge-removed", "two-shapes", "too-few", "too-large", "empty-rows",
         "skewed", "dense"],
)
def test_no_component_layout_unless_all_three_conditions_hold(make):
    w = make()
    assert component_layout(w) is None
    b = jnp.zeros((w.shape[0],), jnp.float32)
    plan = P.build_plan([w], [b], 8, use_resident=False)
    assert plan.component_layers == 0
    assert plan.gathers == (None, None)


def test_component_plan_matches_the_reference_and_the_plain_layout():
    spec = rx.RadixNetSpec(1024, 6)
    ws, bs = _stack(1024, 6)
    y0 = rx.radixnet_input_panel(1024, 40, density=0.3, seed=4)
    ref_y, ref_cats = rx.radixnet_reference(spec, y0)
    plan = P.build_plan(ws, bs, 40, use_resident=False)
    plain = P.build_plan(ws, bs, 40, use_resident=False, relayout=False)
    assert plan.route == P.ROUTE_LAYERED
    assert plan.component_layers == 6 and plain.component_layers == 0
    assert {lp.path for lp in plan.layers} == {"kernel-bcsr"}
    # the bill counts the blocks that run: 32 of 1024x32 a layer
    assert all(w.total_blocks == 32 for w in plan.weights)
    tiles = P.layer_grid_steps(plan.weights[0], 40) // 32
    assert plan.grid_steps == 6 * 32 * tiles
    assert plan.grid_steps < plain.grid_steps
    yj = jnp.asarray(y0)
    out = np.asarray(plan.forward(yj))
    # each row's terms are added one a step in ascending column order,
    # as the reference's CSR product adds them: the same float32 sums
    np.testing.assert_array_equal(out, ref_y)
    np.testing.assert_allclose(
        out, np.asarray(plain.forward(yj)), rtol=1e-4, atol=1e-6
    )
    assert np.array_equal(rx.reference_categories(out), ref_cats)
    assert plan.forward(yj[:, :5]).shape == (1024, 5)  # pads, slices back


def test_a_mixed_radix_2_phase_matches_the_reference_bit_for_bit():
    """2048 = 32**2 * 2: its last phase mixes radix 2 at stride 1024 with
    radix 16, as 65536's does at stride 32768. Forced onto the layered
    route at the 65536 configuration's bias and density, every layer
    takes the component layout and the answers equal the reference's."""
    spec = rx.RadixNetSpec(2048, 6, bias=-0.45)
    assert rx.num_phases(2048) == 3
    ws, bs = rx.radixnet_weights(spec)
    y0 = rx.radixnet_input_panel(2048, 40, density=0.45, seed=5)
    ref_y, ref_cats = rx.radixnet_reference(spec, y0)
    plan = P.build_plan(ws, bs, 40, use_resident=False)
    assert plan.route == P.ROUTE_LAYERED
    assert plan.component_layers == 6
    out = np.asarray(plan.forward(jnp.asarray(y0)))
    np.testing.assert_array_equal(out, ref_y)
    assert 0 < len(ref_cats) < 40  # some inputs live, not all


def test_an_identity_gather_is_skipped():
    """At 1024 neurons phase 0 keeps its rows in natural order, reads
    its columns in the order phase 1 leaves its rows, and phase 1 reads
    natural order: a 0, 1, 0 stack gathers before its first layer only."""
    ws, bs = _stack(1024, 3)
    plan = P.build_plan(ws, bs, 8, use_resident=False)
    assert [lp.gather is None for lp in plan.layers] == [False, True, True]
    assert plan.out_gather is None
    y0 = rx.radixnet_input_panel(1024, 8, density=0.3, seed=6)
    ref_y, _ = rx.radixnet_reference(rx.RadixNetSpec(1024, 3), y0)
    np.testing.assert_array_equal(np.asarray(plan.forward(jnp.asarray(y0))), ref_y)


@pytest.mark.parametrize(
    "kwargs",
    [
        {"differentiable": True},
        {"tuned": TunedConfig(layout="bcsr")},
        {"tuned": TunedConfig(block_size=32)},
        {"use_resident": None},  # the fused route wins first
    ],
    ids=["differentiable", "tuned-layout", "tuned-block-size", "fused"],
)
def test_stays_off_where_the_plan_keeps_the_callers_layout(kwargs):
    ws, bs = _stack(1024, 3)
    kwargs = {"use_resident": False, **kwargs}
    plan = P.build_plan(ws, bs, 8, **kwargs)
    assert plan.component_layers == 0
    assert "component" not in plan.layouts
    assert plan.gathers == (None,) * 4


def test_tuned_block_n_alone_keeps_the_component_layout():
    ws, bs = _stack(1024, 3)
    plan = P.build_plan(ws, bs, 8, use_resident=False, tuned=TunedConfig(block_n=256))
    assert plan.component_layers == 3


def test_a_donor_plan_shares_the_gathers_weights_and_biases():
    ws, bs = _stack(16384, 6)
    cache = P.PlanCache()
    narrow = cache.get(ws, bs, 16, use_resident=False)
    wide = cache.get(ws, bs, 128, use_resident=False)
    assert cache.builds == 2
    assert wide.component_layers == narrow.component_layers == 6
    for a, b in zip(wide.gathers, narrow.gathers):
        assert a is b
    assert all(a is b for a, b in zip(wide.weights, narrow.weights))
    assert all(a is b for a, b in zip(wide.biases, narrow.biases))
    assert wide.grid_steps == narrow.grid_steps  # both one column tile
    # phases 0, 1, 2, 0, 1, 2: phase 1 reads the natural order phase 0
    # leaves, and the gather into phase 2 recurs as one array
    g = narrow.gathers
    assert g[1] is None and g[4] is None
    assert g[2] is g[5] and g[2] is not None and g[0] is not g[3]


def test_describe_shows_the_component_layout():
    ws, bs = _stack(1024, 2)
    desc = P.build_plan(ws, bs, 8, use_resident=False).describe()
    assert desc["layouts"] == ["component", "component"]
    assert desc["component_layers"] == 2
