"""The layered stack route: one kernel launch per layer.

A homogeneous square BSR stack runs the same single-device route as
every other stack: each layer is one fused SpMM + bias + ReLU kernel
launch, and the result matches the layer-by-layer
``dnn_forward(..., fused=True)`` reference to ≤1e-5 (CPU interpret
mode).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import plan as P
from repro.core import dnn
from repro.kernels import ref
from repro.serve import SparseDNNEngine
from repro.sparse import BlockSparseMatrix


def _stack(key, L, m, bpr=3, block=(8, 8), bias_scale=0.5):
    keys = jax.random.split(key, 2 * L)
    # keep magnitudes tame so L-layer products stay O(1) and the 1e-5
    # comparison is meaningful in absolute terms too
    ws = [
        BlockSparseMatrix.random(
            keys[2 * i], (m, m), block, blocks_per_row=bpr
        ).map_blocks(lambda b: b * (0.5 / bpr))
        for i in range(L)
    ]
    bs = [
        jax.random.uniform(
            keys[2 * i + 1], (m,), minval=-bias_scale, maxval=bias_scale
        )
        for i in range(L)
    ]
    return ws, bs


def _forward(ws, bs, y0):
    """The plan's forward at the panel's own width."""
    plan = P.build_plan(ws, bs, y0.shape[1])
    assert plan.route == P.ROUTE_LAYERED
    return plan.forward(y0)


@pytest.mark.parametrize("L", [1, 3, 5])
def test_matches_layered_reference(L):
    ws, bs = _stack(jax.random.PRNGKey(L), L, 64)
    y0 = jax.random.uniform(jax.random.PRNGKey(100 + L), (64, 20))
    expected = dnn.dnn_forward(ws, bs, y0, fused=True)
    np.testing.assert_allclose(
        np.asarray(_forward(ws, bs, y0)), np.asarray(expected),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("width", [13, 32], ids=["ragged", "aligned"])
@pytest.mark.parametrize("L", [1, 4, 8])
@pytest.mark.parametrize("m", [64, 256, 1024])
def test_layered_route_matches_the_layer_reference(m, L, width):
    """Every homogeneous square BSR stack runs one kernel launch a layer
    and matches the layer-by-layer reference."""
    ws, bs = _stack(jax.random.PRNGKey(m + L), L, m, bpr=2, block=(16, 16))
    y0 = jax.random.uniform(jax.random.PRNGKey(m * L + width), (m, width))
    plan = P.build_plan(ws, bs, width)
    assert plan.route == P.ROUTE_LAYERED
    assert plan.pallas_calls == L
    np.testing.assert_allclose(
        np.asarray(plan.forward(y0)),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5, atol=1e-5,
    )


def test_matches_ref_oracle():
    ws, bs = _stack(jax.random.PRNGKey(7), 4, 64, bpr=2)
    y0 = jax.random.uniform(jax.random.PRNGKey(8), (64, 12))
    expected = y0
    for w, b in zip(ws, bs):
        expected = ref.bsr_spmm_ref(w, expected, bias=b, fuse_bias_relu=True)
    np.testing.assert_allclose(
        np.asarray(_forward(ws, bs, y0)), np.asarray(expected),
        rtol=1e-5, atol=1e-5,
    )


def test_single_pallas_call():
    """An L-layer stack's plan launches one kernel per layer: its
    executable lowers to L kernel call sites."""
    L = 6
    ws, bs = _stack(jax.random.PRNGKey(1), L, 32)
    y0 = jax.random.uniform(jax.random.PRNGKey(2), (32, 8))
    plan = P.build_plan(ws, bs, 8)
    assert plan.pallas_calls == L
    # (the jitted wrapper dedups the shared kernel jaxpr, so count the
    # per-layer ``jit`` call sites rather than the pallas_call primitive
    # itself, whose own kernel name sits in the shared binding; the
    # word boundary skips the inner ``..._diff`` names)
    jaxpr = str(jax.make_jaxpr(plan._fn)(*plan._args, y0))
    assert len(re.findall(r"jit\[name=b(?:c)?sr_spmm\b", jaxpr)) == L


def test_relu_and_sparsity_semantics():
    """Outputs non-negative; empty block-rows yield max(bias, 0)."""
    m = 32
    dense = np.zeros((m, m), np.float32)
    dense[:8, :8] = 1.0  # only the first block-row stores anything
    w = BlockSparseMatrix.from_dense(dense, (8, 8))
    ws = [w, w]
    bias = jax.random.normal(jax.random.PRNGKey(3), (m,))
    bs = [bias, bias]
    y0 = jax.random.uniform(jax.random.PRNGKey(4), (m, 8))
    out = _forward(ws, bs, y0)
    assert float(out.min()) >= 0.0
    expected_empty = np.maximum(np.asarray(bias)[8:, None], 0.0)
    np.testing.assert_allclose(
        np.asarray(out)[8:], np.broadcast_to(expected_empty, (m - 8, 8)),
        rtol=1e-6, atol=1e-6,
    )


def test_ragged_batch_padding():
    ws, bs = _stack(jax.random.PRNGKey(5), 3, 64)
    y0 = jax.random.uniform(jax.random.PRNGKey(6), (64, 13))  # ragged n
    out = _forward(ws, bs, y0)
    assert out.shape == (64, 13)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_rejects_non_square():
    """A non-square layer runs layered like any other and matches."""
    w = BlockSparseMatrix.random(
        jax.random.PRNGKey(9), (32, 64), (8, 8), blocks_per_row=2
    )
    b = jax.random.uniform(jax.random.PRNGKey(10), (32,))
    y0 = jax.random.uniform(jax.random.PRNGKey(11), (64, 8))
    np.testing.assert_allclose(
        np.asarray(_forward([w], [b], y0)),
        np.asarray(dnn.dnn_forward([w], [b], y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_serve_engine_empty_batch_is_noop():
    ws, bs = _stack(jax.random.PRNGKey(17), 2, 32, bpr=2)
    eng = SparseDNNEngine(ws, bs, batch_align=16)
    out, stats = eng.infer(jnp.zeros((32, 0)))
    assert out.shape == (32, 0)
    assert stats["pallas_calls"] == 0
    assert stats["served_total"] == 0


def test_serve_engine_fallback_uses_layered_kernels():
    """A mixed-layout stack → one kernel call per layer, same numbers."""
    from repro.sparse import BlockCSRMatrix

    m = 64
    ws, bs = _stack(jax.random.PRNGKey(18), 2, m, bpr=2)
    mixed = [BlockCSRMatrix.from_bsr(ws[0]), ws[1]]  # mixed layout
    eng = SparseDNNEngine(mixed, bs, batch_align=16)
    y0 = jax.random.uniform(jax.random.PRNGKey(19), (m, 8))
    out, stats = eng.infer(y0)
    assert stats["plan"]["route"] == P.ROUTE_LAYERED
    assert stats["pallas_calls"] == 2
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dnn.dnn_forward(mixed, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_serve_engine_resident():
    """An engine over a homogeneous square stack builds the layered
    plan at the layered level."""
    ws, bs = _stack(jax.random.PRNGKey(15), 3, 64)
    eng = SparseDNNEngine(ws, bs, batch_align=16)
    y0 = jax.random.uniform(jax.random.PRNGKey(16), (64, 10))
    out, stats = eng.infer(y0)
    assert stats["plan"]["route"] == P.ROUTE_LAYERED
    assert stats["plan"]["level"] == P.LEVEL_LAYERED
    assert stats["pallas_calls"] == 3
    assert stats["padded_batch"] == 16
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )
