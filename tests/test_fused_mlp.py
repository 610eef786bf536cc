"""VMEM-resident multi-layer fused forward: correctness + single-call.

The acceptance contract: one ``pallas_call`` for an L-layer stack, and
the result matches the layered ``dnn_forward(..., fused=True)``
reference to ≤1e-5 (CPU interpret mode).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dnn
from repro.kernels import fused_mlp
from repro.kernels import ops, ref
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


@pytest.mark.parametrize("L", [1, 3, 5])
def test_matches_layered_reference(L):
    ws, bs = _stack(jax.random.PRNGKey(L), L, 64)
    y0 = jax.random.uniform(jax.random.PRNGKey(100 + L), (64, 20))
    out = ops.fused_mlp_forward(dnn.stack_bsr(ws), jnp.stack(bs), y0)
    expected = dnn.dnn_forward(ws, bs, y0, fused=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(expected), rtol=1e-5, atol=1e-5
    )


def test_matches_ref_oracle():
    ws, bs = _stack(jax.random.PRNGKey(7), 4, 64, bpr=2)
    stacked_w, stacked_b = dnn.stack_bsr(ws), jnp.stack(bs)
    y0 = jax.random.uniform(jax.random.PRNGKey(8), (64, 12))
    np.testing.assert_allclose(
        np.asarray(ops.fused_mlp_forward(stacked_w, stacked_b, y0)),
        np.asarray(ref.fused_mlp_forward_ref(stacked_w, stacked_b, y0)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_single_pallas_call():
    """An L-layer stack must lower to exactly ONE pallas_call."""
    L = 6
    ws, bs = _stack(jax.random.PRNGKey(1), L, 32)
    stacked_w, stacked_b = dnn.stack_bsr(ws), jnp.stack(bs)
    y0 = jax.random.uniform(jax.random.PRNGKey(2), (32, 8))
    jaxpr = jax.make_jaxpr(
        lambda w, b, y: ops.fused_mlp_forward(w, b, y)
    )(stacked_w, stacked_b, y0)
    assert str(jaxpr).count("pallas_call") == 1

    # while the layered kernel path pays one call PER layer
    def layered(ws_, bs_, y):
        for w, b in zip(ws_, bs_):
            y = ops.bsr_spmm(w, y, b, fuse_bias_relu=True)
        return y

    # (the jitted wrapper dedups the shared kernel jaxpr, so count the
    # per-layer ``jit`` call sites rather than the pallas_call primitive
    # itself, whose own ``name=bsr_spmm`` sits in the shared binding; the
    # word boundary skips its inner ``name=bsr_spmm_diff``)
    jaxpr_layered = jax.make_jaxpr(layered)(ws, bs, y0)
    assert len(re.findall(r"jit\[name=bsr_spmm\b", str(jaxpr_layered))) == L


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
    out = ops.fused_mlp_forward(dnn.stack_bsr(ws), jnp.stack(bs), y0)
    assert float(out.min()) >= 0.0
    expected_empty = np.maximum(np.asarray(bias)[8:, None], 0.0)
    np.testing.assert_allclose(
        np.asarray(out)[8:], np.broadcast_to(expected_empty, (m - 8, 8)),
        rtol=1e-6, atol=1e-6,
    )


def test_ragged_batch_padding():
    ws, bs = _stack(jax.random.PRNGKey(5), 3, 64)
    y0 = jax.random.uniform(jax.random.PRNGKey(6), (64, 13))  # ragged n
    out = ops.fused_mlp_forward(dnn.stack_bsr(ws), jnp.stack(bs), y0)
    assert out.shape == (64, 13)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_rejects_non_square():
    w = BlockSparseMatrix.random(
        jax.random.PRNGKey(9), (32, 64), (8, 8), blocks_per_row=2
    )
    stacked = dnn.stack_bsr([w])
    y0 = jnp.ones((64, 8))
    with pytest.raises(ValueError):
        fused_mlp.fused_mlp_forward(stacked, jnp.zeros((1, 32)), y0)


def test_eligibility_gate():
    small = BlockSparseMatrix.random(
        jax.random.PRNGKey(10), (64, 64), (8, 8), blocks_per_row=2
    )
    assert fused_mlp.fused_mlp_eligible(small)
    rect = BlockSparseMatrix.random(
        jax.random.PRNGKey(11), (64, 128), (8, 8), blocks_per_row=2
    )
    assert not fused_mlp.fused_mlp_eligible(rect)
    # VMEM ceiling: a panel too tall must be rejected
    assert (
        fused_mlp.fused_mlp_vmem_bytes(64 * 1024)
        > fused_mlp.VMEM_SOFT_LIMIT_BYTES
    )


def test_dnn_forward_resident_fallback():
    """Ineligible stacks silently take the layered path, same numbers."""
    m = 48
    ws, bs = _stack(jax.random.PRNGKey(12), 2, m, bpr=2)
    # heterogeneous pad width → ineligible
    ws = [ws[0], BlockSparseMatrix.random(
        jax.random.PRNGKey(13), (m, m), (8, 8), blocks_per_row=4
    )]
    assert not dnn.resident_eligible(ws)
    y0 = jax.random.uniform(jax.random.PRNGKey(14), (m, 8))
    np.testing.assert_allclose(
        dnn.dnn_forward_resident(ws, bs, y0),
        dnn.dnn_forward(ws, bs, y0, fused=True),
        rtol=1e-6,
    )


def test_serve_engine_empty_batch_is_noop():
    ws, bs = _stack(jax.random.PRNGKey(17), 2, 32, bpr=2)
    eng = SparseDNNEngine(ws, bs, batch_align=16)
    out, stats = eng.infer(jnp.zeros((32, 0)))
    assert out.shape == (32, 0)
    assert stats["pallas_calls"] == 0
    assert stats["served_total"] == 0


def test_serve_engine_fallback_uses_layered_kernels():
    """Ineligible stack → one kernel call per layer, same numbers."""
    from repro.sparse import BlockCSRMatrix

    m = 64
    ws, bs = _stack(jax.random.PRNGKey(18), 2, m, bpr=2)
    mixed = [BlockCSRMatrix.from_bsr(ws[0]), ws[1]]  # mixed layout
    eng = SparseDNNEngine(mixed, bs, batch_align=16)
    y0 = jax.random.uniform(jax.random.PRNGKey(19), (m, 8))
    out, stats = eng.infer(y0)
    assert stats["resident"] is False
    assert stats["pallas_calls"] == 2
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dnn.dnn_forward(mixed, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_serve_engine_rejects_forced_resident_on_ineligible_stack():
    from repro.sparse import BlockCSRMatrix

    ws, bs = _stack(jax.random.PRNGKey(20), 2, 32, bpr=2)
    mixed = [BlockCSRMatrix.from_bsr(ws[0]), ws[1]]
    with pytest.raises(ValueError):
        SparseDNNEngine(mixed, bs, use_resident=True)


def test_serve_engine_resident():
    ws, bs = _stack(jax.random.PRNGKey(15), 3, 64)
    eng = SparseDNNEngine(ws, bs, batch_align=16)
    y0 = jax.random.uniform(jax.random.PRNGKey(16), (64, 10))
    out, stats = eng.infer(y0)
    assert stats["resident"] is True
    assert stats["pallas_calls"] == 1
    assert stats["padded_batch"] == 16
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )
