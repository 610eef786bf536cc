"""Gradient coverage for the sparse kernel layer (custom VJPs).

Checks, per layout (ELL-BSR and block-CSR), in interpret mode:
  * jax.grad through the kernel wrappers == dense jax.grad reference;
  * finite-difference validation (jax.test_util.check_grads, rev mode);
  * the weight cotangent's sparsity pattern equals the primal's
    (padded/invalid slots exactly zero — the no-densify invariant);
  * grad through models.layers.linear matches dense to 1e-4;
  * the sparse train step decreases loss with kernels in the hot path;
  * a differentiable serve engine keeps the caller's layouts and routes
    dense layers through the XLA form.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.test_util import check_grads

from repro.core import dnn
from repro.kernels import ops
from repro.models import layers
from repro.sparse import BlockCSRMatrix, BlockSparseMatrix
from repro.sparse import ops as sparse_ops


def _random_bsr(key, shape, block, bpr, scale=0.3):
    a = BlockSparseMatrix.random(key, shape, block, blocks_per_row=bpr)
    return a.map_blocks(lambda x: x * scale)


def _skewed_bcsr(m, k, block):
    """Block-CSR with an empty block-row AND invalid tail padding — the
    two structural edge cases of the layout."""
    nrb, ncb = m // block, k // block
    dense = np.zeros((m, k), np.float32)
    rng = np.random.default_rng(0)
    for i in range(nrb):
        if i == 1:
            continue  # empty block-row
        cols = rng.choice(ncb, size=min(2 + (i % 2), ncb), replace=False)
        for c in cols:
            dense[i * block:(i + 1) * block, c * block:(c + 1) * block] = (
                rng.uniform(-0.5, 0.5, (block, block))
            )
    c = BlockCSRMatrix.from_dense(jnp.asarray(dense), (block, block))
    return BlockCSRMatrix.from_dense(
        jnp.asarray(dense), (block, block), pad_to=c.total_blocks + 3
    )


BSR_GRAD_CASES = [
    (32, 48, (8, 8), 2),
    (32, 64, (8, 16), 3),  # rectangular blocks
]


@pytest.mark.parametrize("m,k,block,bpr", BSR_GRAD_CASES)
@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused_relu"])
def test_bsr_spmm_grad_matches_dense(m, k, block, bpr, fused):
    a = _random_bsr(jax.random.PRNGKey(m + k), (m, k), block, bpr)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, 20))
    bias = jax.random.normal(jax.random.PRNGKey(2), (m,))

    def loss_kernel(blocks, b_, bias_):
        aa = BlockSparseMatrix(blocks, a.col_idx, a.block_mask, a.shape, a.block_shape)
        out = ops.bsr_spmm(aa, b_, bias_ if fused else None, fuse_bias_relu=fused)
        return jnp.sum(jnp.sin(out))

    def loss_dense(blocks, b_, bias_):
        aa = BlockSparseMatrix(blocks, a.col_idx, a.block_mask, a.shape, a.block_shape)
        z = aa.to_dense() @ b_
        if fused:
            z = jnp.maximum(z + bias_[:, None], 0.0)
        return jnp.sum(jnp.sin(z))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(a.blocks, b, bias)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(a.blocks, b, bias)
    for got, want in zip(gk, gd):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused_relu"])
def test_bcsr_spmm_grad_matches_dense(fused):
    c = _skewed_bcsr(48, 32, 8)
    b = jax.random.normal(jax.random.PRNGKey(3), (32, 24))
    bias = jax.random.normal(jax.random.PRNGKey(4), (48,))

    def loss_kernel(values, b_, bias_):
        cc = BlockCSRMatrix(
            values, c.row_ptr, c.row_id, c.col_idx, c.valid, c.shape, c.block_shape
        )
        out = ops.bcsr_spmm(cc, b_, bias_ if fused else None, fuse_bias_relu=fused)
        return jnp.sum(jnp.cos(out))

    def loss_dense(values, b_, bias_):
        cc = BlockCSRMatrix(
            values, c.row_ptr, c.row_id, c.col_idx, c.valid, c.shape, c.block_shape
        )
        z = cc.to_dense() @ b_
        if fused:
            z = jnp.maximum(z + bias_[:, None], 0.0)
        return jnp.sum(jnp.cos(z))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(c.values, b, bias)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(c.values, b, bias)
    for got, want in zip(gk, gd):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_bsr_spmm_finite_differences():
    a = _random_bsr(jax.random.PRNGKey(5), (16, 16), (8, 8), 2)
    b = jax.random.normal(jax.random.PRNGKey(6), (16, 8))

    def f(blocks, b_):
        aa = BlockSparseMatrix(blocks, a.col_idx, a.block_mask, a.shape, a.block_shape)
        return ops.bsr_spmm(aa, b_)

    check_grads(f, (a.blocks, b), order=1, modes=["rev"], atol=1e-2, rtol=1e-2)


def test_bcsr_spmm_finite_differences():
    c = _skewed_bcsr(16, 16, 8)
    b = jax.random.normal(jax.random.PRNGKey(7), (16, 8))

    def f(values, b_):
        cc = BlockCSRMatrix(
            values, c.row_ptr, c.row_id, c.col_idx, c.valid, c.shape, c.block_shape
        )
        return ops.bcsr_spmm(cc, b_)

    check_grads(f, (c.values, b), order=1, modes=["rev"], atol=1e-2, rtol=1e-2)


def test_weight_cotangent_pattern_equals_primal():
    """Regression: the gradient lives EXACTLY in the primal's pattern."""
    # ELL with widened padding (garbage-free invalid slots)
    a = _random_bsr(jax.random.PRNGKey(8), (32, 32), (8, 8), 2)
    wide = BlockSparseMatrix.from_dense(a.to_dense(), (8, 8), pad_to=4)
    assert not bool(wide.block_mask.all())
    b = jax.random.normal(jax.random.PRNGKey(9), (32, 12))

    g = jax.grad(
        lambda aa: jnp.sum(ops.bsr_spmm(aa, b) ** 2), allow_int=True
    )(wide)
    assert isinstance(g, BlockSparseMatrix)
    off_pattern = jnp.where(wide.block_mask[:, :, None, None], 0.0, g.blocks)
    assert float(jnp.abs(off_pattern).max()) == 0.0
    on_pattern = jnp.where(wide.block_mask[:, :, None, None], g.blocks, 0.0)
    assert float(jnp.abs(on_pattern).max()) > 0.0

    # block-CSR with invalid tail slots
    c = _skewed_bcsr(32, 32, 8)
    assert not bool(c.valid.all())
    gc = jax.grad(
        lambda cc: jnp.sum(ops.bcsr_spmm(cc, b) ** 2), allow_int=True
    )(c)
    assert isinstance(gc, BlockCSRMatrix)
    assert float(jnp.abs(jnp.where(c.valid[:, None, None], 0.0, gc.values)).max()) == 0.0
    assert float(jnp.abs(gc.values).max()) > 0.0
    # integer topology leaves come back as float0 (frozen under training)
    assert gc.col_idx.dtype == jax.dtypes.float0


def test_transpose_matmul_helpers_match_dense():
    a = _random_bsr(jax.random.PRNGKey(10), (32, 48), (8, 8), 3)
    y = jax.random.normal(jax.random.PRNGKey(11), (32, 10))
    np.testing.assert_allclose(
        sparse_ops.bsr_transpose_matmul(a, y),
        a.to_dense().T @ y,
        rtol=1e-5,
        atol=1e-5,
    )
    c = _skewed_bcsr(48, 32, 8)
    yc = jax.random.normal(jax.random.PRNGKey(12), (48, 10))
    np.testing.assert_allclose(
        sparse_ops.bcsr_transpose_matmul(c, yc),
        c.to_dense().T @ yc,
        rtol=1e-5,
        atol=1e-5,
    )


@pytest.mark.parametrize("use_kernel", [True, False], ids=["pallas", "xla"])
def test_linear_grad_bcsr_matches_dense(use_kernel):
    """Acceptance: jax.grad through linear() on a BCSR weight == dense
    reference to 1e-4, with no dense weight materialized in the path."""
    c = _skewed_bcsr(32, 48, 8)  # (d_out, d_in) output-major
    x = jax.random.normal(jax.random.PRNGKey(13), (5, 48))
    bias = jax.random.normal(jax.random.PRNGKey(14), (32,))
    w_dense = c.to_dense()  # test-only reference

    def loss_sparse(values, x_, bias_):
        cc = BlockCSRMatrix(
            values, c.row_ptr, c.row_id, c.col_idx, c.valid, c.shape, c.block_shape
        )
        return jnp.sum(layers.linear(cc, x_, bias_, use_kernel=use_kernel) ** 2)

    def loss_dense(w, x_, bias_):
        return jnp.sum((x_ @ w.T + bias_) ** 2)

    gs = jax.grad(loss_sparse, argnums=(0, 1, 2))(c.values, x, bias)
    gd = jax.grad(loss_dense, argnums=(1, 2))(w_dense, x, bias)
    np.testing.assert_allclose(gs[1], gd[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gs[2], gd[1], rtol=1e-4, atol=1e-4)
    # weight cotangent: compare against dense dW sampled at stored blocks
    dw_dense = jax.grad(lambda w: jnp.sum((x @ w.T + bias) ** 2))(w_dense)
    bs = c.block_shape[0]
    tiles = dw_dense.reshape(32 // bs, bs, 48 // bs, bs).transpose(0, 2, 1, 3)
    want = jnp.where(
        c.valid[:, None, None], tiles[c.row_id, c.col_idx], 0.0
    )
    np.testing.assert_allclose(gs[0], want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("use_kernel", [True, False], ids=["pallas", "xla"])
def test_linear_grad_bsr_matches_dense(use_kernel):
    a = _random_bsr(jax.random.PRNGKey(15), (32, 48), (8, 8), 2)
    x = jax.random.normal(jax.random.PRNGKey(16), (3, 48))

    def loss_sparse(blocks, x_):
        aa = BlockSparseMatrix(blocks, a.col_idx, a.block_mask, a.shape, a.block_shape)
        return jnp.sum(layers.linear(aa, x_, use_kernel=use_kernel) ** 2)

    def loss_dense(blocks, x_):
        aa = BlockSparseMatrix(blocks, a.col_idx, a.block_mask, a.shape, a.block_shape)
        return jnp.sum((x_ @ aa.to_dense().T) ** 2)

    gs = jax.grad(loss_sparse, argnums=(0, 1))(a.blocks, x)
    gd = jax.grad(loss_dense, argnums=(0, 1))(a.blocks, x)
    np.testing.assert_allclose(gs[0], gd[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gs[1], gd[1], rtol=1e-4, atol=1e-4)


def test_sparse_train_step_decreases_loss():
    from repro.train.optimizer import sgd
    from repro.train.sparse import (
        grad_sparsity_preserved,
        init_sparse_mlp_state,
        make_sparse_train_step,
    )

    m, n = 32, 16
    ws = [
        _random_bsr(jax.random.PRNGKey(20), (m, m), (8, 8), 2),
        BlockCSRMatrix.from_bsr(_random_bsr(jax.random.PRNGKey(21), (m, m), (8, 8), 2)),
    ]
    bs = [jnp.zeros((m,)) for _ in ws]
    y0 = jax.random.uniform(jax.random.PRNGKey(22), (m, n))
    targets = jax.random.uniform(jax.random.PRNGKey(23), (m, n))
    batch = {"y0": y0, "targets": targets}

    opt = sgd(1.0, momentum=0.0)
    state = init_sparse_mlp_state(ws, bs, opt)
    step = jax.jit(make_sparse_train_step(opt, use_kernel=True))
    losses = []
    for _ in range(4):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    # topology untouched by training
    assert isinstance(state.weights[0], BlockSparseMatrix)
    assert isinstance(state.weights[1], BlockCSRMatrix)
    np.testing.assert_array_equal(state.weights[0].col_idx, ws[0].col_idx)
    np.testing.assert_array_equal(state.weights[1].row_id, ws[1].row_id)

    # and the cotangents live in the primal pattern
    _, grads = jax.value_and_grad(
        lambda p: 0.5
        * jnp.mean(
            (dnn.dnn_forward_trainable(p[0], p[1], y0) - targets) ** 2
        ),
        allow_int=True,
    )((state.weights, state.biases))
    assert grad_sparsity_preserved(state.weights, grads[0])


def test_dnn_value_and_grad():
    m, n = 32, 8
    ws = [_random_bsr(jax.random.PRNGKey(30), (m, m), (8, 8), 2)]
    bs = [jnp.zeros((m,))]
    y0 = jax.random.uniform(jax.random.PRNGKey(31), (m, n))
    targets = jnp.zeros((m, n))
    loss, (dws, dbs) = dnn.dnn_value_and_grad(ws, bs, y0, targets)
    assert float(loss) >= 0.0
    assert isinstance(dws[0], BlockSparseMatrix)
    assert dbs[0].shape == (m,)
    # matches the XLA-oracle gradient path
    loss2, (dws2, dbs2) = dnn.dnn_value_and_grad(
        ws, bs, y0, targets, use_kernel=False
    )
    np.testing.assert_allclose(float(loss), float(loss2), rtol=1e-5)
    np.testing.assert_allclose(dws[0].blocks, dws2[0].blocks, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dbs[0], dbs2[0], rtol=1e-4, atol=1e-5)


def test_serve_engine_differentiable_routing():
    from repro.serve.engine import SparseDNNEngine

    ws = [_random_bsr(jax.random.PRNGKey(50), (32, 32), (8, 8), 2) for _ in range(2)]
    bs = [jnp.zeros((32,)) for _ in ws]
    # a homogeneous square stack serves layered, on the caller's layouts
    eng = SparseDNNEngine(ws, bs, batch_align=8, differentiable=True)
    y0 = jax.random.uniform(jax.random.PRNGKey(51), (32, 4))
    out, stats = eng.infer(y0)
    assert stats["plan"]["route"] == "layered"
    assert stats["differentiable"] is True
    assert stats["pallas_calls"] == 2
    (plan,) = eng.plan_cache.plans()
    assert plan.layouts == ("ell", "ell")
    assert out.shape == (32, 4)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dnn.dnn_forward(ws, bs, y0, fused=True)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_serve_engine_differentiable_with_dense_layer():
    """Regression: a dense layer in a differentiable engine must route
    through the XLA fused form (the dense Pallas kernel has no VJP)."""
    from repro.serve.engine import SparseDNNEngine

    ws = [
        _random_bsr(jax.random.PRNGKey(60), (32, 32), (8, 8), 2),
        jax.random.normal(jax.random.PRNGKey(61), (32, 32)) * 0.1,
    ]
    bs = [jnp.zeros((32,)) for _ in ws]
    eng = SparseDNNEngine(ws, bs, batch_align=4, differentiable=True)
    y0 = jax.random.uniform(jax.random.PRNGKey(62), (32, 4))
    g = jax.grad(lambda y: jnp.sum(eng.infer(y)[0]))(y0)
    assert g.shape == y0.shape
    assert float(jnp.abs(g).max()) > 0.0
