"""The paper's ReLU DNN (§III/§IV): faithful vs fused vs sparse paths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dnn
from repro.sparse import BlockSparseMatrix


def _mk_net(key, L, m, sparse=False, bpr=2):
    keys = jax.random.split(key, 2 * L)
    ws, bs = [], []
    for k in range(L):
        if sparse:
            ws.append(
                BlockSparseMatrix.random(
                    keys[2 * k], (m, m), (8, 8), blocks_per_row=bpr
                )
            )
        else:
            ws.append(
                jax.random.uniform(
                    keys[2 * k], (m, m), minval=-1.0, maxval=3.0
                )
            )
        bs.append(jax.random.uniform(keys[2 * k + 1], (m,)))
    return ws, bs


def _numpy_forward(ws, bs, y0):
    y = np.asarray(y0)
    for w, b in zip(ws, bs):
        wd = np.asarray(w.to_dense() if hasattr(w, "to_dense") else w)
        y = np.maximum(wd @ y + np.asarray(b)[:, None], 0.0)
    return y


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "bsr"])
@pytest.mark.parametrize("fused", [False, True], ids=["faithful", "fused"])
def test_forward_matches_numpy(sparse, fused):
    key = jax.random.PRNGKey(0)
    ws, bs = _mk_net(key, L=3, m=32, sparse=sparse)
    y0 = jax.random.uniform(jax.random.PRNGKey(1), (32, 8))
    out = dnn.dnn_forward(ws, bs, y0, fused=fused)
    np.testing.assert_allclose(
        out, _numpy_forward(ws, bs, y0), rtol=1e-4, atol=1e-4
    )


def test_faithful_equals_fused():
    """The fused beyond-paper path must be numerically identical."""
    key = jax.random.PRNGKey(2)
    ws, bs = _mk_net(key, L=4, m=24)
    y0 = jax.random.uniform(jax.random.PRNGKey(3), (24, 6))
    np.testing.assert_allclose(
        dnn.dnn_forward(ws, bs, y0, fused=False),
        dnn.dnn_forward(ws, bs, y0, fused=True),
        rtol=1e-5,
        atol=1e-5,
    )


def test_outputs_nonnegative():
    key = jax.random.PRNGKey(4)
    ws, bs = _mk_net(key, L=2, m=16)
    y0 = jax.random.uniform(jax.random.PRNGKey(5), (16, 4))
    out = dnn.dnn_forward(ws, bs, y0)
    assert float(out.min()) >= 0.0  # ReLU semantics via max-plus ⊕


def test_forward_all_returns_every_layer():
    key = jax.random.PRNGKey(6)
    ws, bs = _mk_net(key, L=3, m=16)
    y0 = jax.random.uniform(jax.random.PRNGKey(7), (16, 4))
    ys = dnn.dnn_forward_all(ws, bs, y0)
    assert len(ys) == 4
    np.testing.assert_array_equal(ys[0], y0)
    np.testing.assert_allclose(
        ys[-1], dnn.dnn_forward(ws, bs, y0), rtol=1e-6
    )


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "bsr"])
def test_scan_equals_loop(sparse):
    key = jax.random.PRNGKey(8)
    ws, bs = _mk_net(key, L=5, m=32, sparse=sparse)
    y0 = jax.random.uniform(jax.random.PRNGKey(9), (32, 8))
    # a BSR stack scans as one pytree whose leaves carry a leading L axis
    stacked_w = jax.tree.map(lambda *leaves: jnp.stack(leaves), *ws)
    stacked_b = jnp.stack(bs)
    out_scan = dnn.dnn_forward_scan(stacked_w, stacked_b, y0)
    out_loop = dnn.dnn_forward(ws, bs, y0)
    np.testing.assert_allclose(out_scan, out_loop, rtol=1e-4, atol=1e-4)


def test_scan_jits_once_for_any_depth():
    """Scan keeps the traced graph depth-independent (dry-run requirement)."""
    key = jax.random.PRNGKey(10)
    y0 = jax.random.uniform(jax.random.PRNGKey(11), (16, 4))
    traces = []

    @jax.jit
    def fwd(ws, bs, y0):
        traces.append(1)
        return dnn.dnn_forward_scan(ws, bs, y0)

    for L in (2, 2):  # same depth → one trace
        ws, bs = _mk_net(key, L=L, m=16)
        fwd(jnp.stack(ws), jnp.stack(bs), y0)
    assert len(traces) == 1


def test_sparse_dense_agree_on_same_weights():
    """BSR forward == dense forward when BSR stores the same matrix."""
    key = jax.random.PRNGKey(13)
    ws_sp, bs = _mk_net(key, L=2, m=32, sparse=True, bpr=2)
    ws_dn = [w.to_dense() for w in ws_sp]
    y0 = jax.random.uniform(jax.random.PRNGKey(14), (32, 8))
    np.testing.assert_allclose(
        dnn.dnn_forward(ws_sp, bs, y0),
        dnn.dnn_forward(ws_dn, bs, y0),
        rtol=1e-4,
        atol=1e-4,
    )
