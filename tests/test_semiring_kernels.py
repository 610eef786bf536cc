"""Semiring-generalized sparse kernels vs the algebra's own matmul.

The tentpole contract: every registry semiring × both sparse layouts
(block-CSR and ELL) through the Pallas kernels must match
``Semiring.matmul`` on the dense reconstruction — *bit-exactly* in f32
for the order-independent semirings (integer-valued inputs make
plus_times sums exact too), to 1e-5 for ``log_plus`` (the kernel chains
chunked logsumexp reductions where the reference does one). The dense
reference fills entries outside stored blocks with the semiring's ⊕
identity — NOT 0.0 — because a missing block means "no edge" in every
algebra (for ``min_plus``, 0.0 would be a free edge).

Topologies are built to exercise the two hazard cases the kernels must
get right for non-additive monoids:

* **empty rows** — a block-row with no stored blocks must come out as
  the ⊕ identity (the bcsr wrapper's fill), not garbage;
* **padded blocks** — ELL pad slots and bcsr tail padding must be
  annihilator-aware: skipped entirely, contributing exactly the ⊕
  identity to their accumulator.

Also pins the GraphBLAS façade routing: ``mxm`` on a sparse operand
launches the Pallas kernel route (pallas_call-counted), the oracle
route launches none, and plans are cached per (topology, width,
semiring).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graphblas as gb
from repro.core import semiring as core_sr
from repro.kernels import ops as kernel_ops
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

ALL_NAMES = sorted(core_sr.REGISTRY)
# log_plus: the kernel's chunked logsumexp chain vs the reference's
# single reduction — equal to f32 roundoff, not bit-equal.
TOL = {"log_plus": 1e-5}


def _assert_matches(name, out, ref):
    ref = np.asarray(ref, np.float32)
    out = np.asarray(out, np.float32)
    if name in TOL:
        np.testing.assert_allclose(out, ref, rtol=TOL[name], atol=TOL[name])
    else:
        np.testing.assert_array_equal(out, ref)


def _integer_dense(seed, shape, block_shape, zero_block_rows=()):
    """Integer-valued f32 dense with block structure and empty rows."""
    rng = np.random.default_rng(seed)
    d = rng.integers(-3, 4, size=shape).astype(np.float32)
    bs_r, _ = block_shape
    # knock out some whole blocks so the sparse forms have gaps
    nrb, ncb = shape[0] // block_shape[0], shape[1] // block_shape[1]
    keep = rng.random((nrb, ncb)) < 0.5
    keep[:, 0] = True  # every column represented somewhere
    for rb in zero_block_rows:
        keep[rb, :] = False  # an EMPTY block-row
    mask = np.kron(keep, np.ones(block_shape, bool))
    return np.where(mask, d, 0.0).astype(np.float32), keep


def _reference(sr, dense, present, b):
    """Semiring.matmul on the ⊕-identity-filled dense reconstruction."""
    a_ref = jnp.where(jnp.asarray(present), jnp.asarray(dense), sr.zero)
    ref = sr.matmul(a_ref, jnp.asarray(b))
    if ref.dtype == jnp.bool_:
        ref = ref.astype(jnp.float32)
    return ref


def _present_mask(keep, block_shape):
    return np.kron(keep, np.ones(block_shape, bool))


def _b_panel(seed, k, n, name):
    rng = np.random.default_rng(seed)
    b = rng.integers(-3, 4, size=(k, n)).astype(np.float32)
    if name in ("lor_land", "xor_and"):
        b = (b > 0).astype(np.float32)  # {0,1} encoding
    return b


def _a_values(dense, name):
    if name in ("lor_land", "xor_and"):
        return (dense > 0).astype(np.float32)
    return dense


M, K, N = 48, 32, 24
BLOCK = (8, 8)


@pytest.mark.parametrize("name", ALL_NAMES)
def test_bcsr_kernel_matches_semiring_matmul(name):
    sr = core_sr.get_semiring(name)
    dense, keep = _integer_dense(3, (M, K), BLOCK, zero_block_rows=(2,))
    dense = _a_values(dense, name)
    present = _present_mask(keep, BLOCK)
    # tail padding past the real block count = padded invalid blocks
    a = BlockCSRMatrix.from_dense(
        jnp.asarray(dense), BLOCK, pad_to=int(keep.sum()) + 5
    )
    b = _b_panel(4, K, N, name)
    out = kernel_ops.bcsr_spmm(a, jnp.asarray(b), semiring_name=name)
    _assert_matches(name, out, _reference(sr, dense, present, b))


@pytest.mark.parametrize("name", ALL_NAMES)
def test_bsr_kernel_matches_semiring_matmul(name):
    sr = core_sr.get_semiring(name)
    dense, keep = _integer_dense(5, (M, K), BLOCK, zero_block_rows=(1,))
    dense = _a_values(dense, name)
    present = _present_mask(keep, BLOCK)
    # ELL: rows with fewer blocks than the max carry masked pad slots
    a = BlockSparseMatrix.from_dense(jnp.asarray(dense), BLOCK)
    assert a.block_mask.size > int(keep.sum())  # pad slots exist
    b = _b_panel(6, K, N, name)
    out = kernel_ops.bsr_spmm(a, jnp.asarray(b), semiring_name=name)
    _assert_matches(name, out, _reference(sr, dense, present, b))


@pytest.mark.parametrize("name", ALL_NAMES)
def test_empty_rows_produce_identity(name):
    """A block-row with no stored blocks is pure ⊕-identity output."""
    sr = core_sr.get_semiring(name)
    dense, keep = _integer_dense(7, (M, K), BLOCK, zero_block_rows=(0, 4))
    dense = _a_values(dense, name)
    b = _b_panel(8, K, N, name)
    a = BlockCSRMatrix.from_dense(jnp.asarray(dense), BLOCK)
    out = np.asarray(kernel_ops.bcsr_spmm(a, jnp.asarray(b), semiring_name=name))
    bs_r = BLOCK[0]
    for rb in (0, 4):
        expect = sr.add_reduce(
            jnp.full((N, 1), sr.zero, jnp.float32), axis=-1
        )  # reduce over an all-identity set == the identity
        row = out[rb * bs_r : (rb + 1) * bs_r]
        want = np.full_like(row, float(np.asarray(expect)[0]))
        np.testing.assert_array_equal(row, want)


@pytest.mark.parametrize("name", ALL_NAMES)
def test_dense_kernel_matches_semiring_matmul(name):
    sr = core_sr.get_semiring(name)
    rng = np.random.default_rng(11)
    a = rng.integers(-3, 4, size=(40, 24)).astype(np.float32)
    a = _a_values(a, name)
    b = _b_panel(12, 24, 16, name)
    out = kernel_ops.semiring_matmul(
        jnp.asarray(a), jnp.asarray(b), semiring_name=name
    )
    ref = sr.matmul(jnp.asarray(a), jnp.asarray(b))
    if ref.dtype == jnp.bool_:
        ref = ref.astype(jnp.float32)
    _assert_matches(name, out, ref)


def test_unknown_semiring_fails_fast():
    a = BlockSparseMatrix.random(jax.random.PRNGKey(0), (16, 16), (8, 8), 1)
    b = jnp.zeros((16, 8), jnp.float32)
    with pytest.raises(KeyError):
        kernel_ops.bsr_spmm(a, b, semiring_name="no_such_algebra")


@pytest.mark.parametrize(
    "b_dtype,precision",
    [(jnp.float32, "HIGHEST"), (jnp.bfloat16, "DEFAULT")],
    ids=["f32", "bf16-panel"],
)
def test_mxu_dot_precision(b_dtype, precision):
    """The MXU product asks for full f32 precision whenever both
    operands are f32, whatever the process-wide matmul precision: the
    kernel default rounds f32 operands to bf16, which changed RadiX-net
    categories on a v5e. A bf16 panel keeps the one-pass product."""
    from repro.kernels.semirings import mxu_dot

    with jax.default_matmul_precision("bfloat16"):
        jaxpr = jax.make_jaxpr(mxu_dot)(
            jnp.zeros((16, 16), jnp.float32), jnp.zeros((16, 128), b_dtype)
        )
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    assert eqn.params["precision"] == (
        getattr(jax.lax.Precision, precision),
    ) * 2
    assert eqn.params["preferred_element_type"] == jnp.float32


# --- graphblas façade routing -------------------------------------------


def _ell(seed=0, m=64, bpr=3):
    return BlockSparseMatrix.random(
        jax.random.PRNGKey(seed), (m, m), (8, 8), blocks_per_row=bpr
    )


def test_mxm_sparse_launches_kernel_route():
    a = _ell()
    b = jnp.ones((64, 16), jnp.float32)
    kernel_jaxpr = str(jax.make_jaxpr(lambda y: gb.mxm(a, y))(b))
    oracle_jaxpr = str(
        jax.make_jaxpr(lambda y: gb.mxm(a, y, use_kernel=False))(b)
    )
    assert kernel_jaxpr.count("pallas_call") >= 1
    assert oracle_jaxpr.count("pallas_call") == 0


@pytest.mark.parametrize("name", ["plus_times", "min_plus", "lor_land"])
def test_mxm_kernel_route_matches_oracle(name):
    sr = core_sr.get_semiring(name)
    a = _ell(seed=2)
    a = BlockSparseMatrix(
        jnp.round(a.blocks * 3), a.col_idx, a.block_mask, a.shape,
        a.block_shape,
    )
    b = jnp.round(
        jax.random.uniform(jax.random.PRNGKey(3), (64, 16), jnp.float32) * 4
    )
    if name == "lor_land":
        b = (b > 1).astype(jnp.float32)
    out_k = gb.mxm(a, b, sr)
    out_o = gb.mxm(a, b, sr, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_o))


def test_mxm_plan_cache_semiring_aware():
    from repro.plan.mxm import mxm_cache_stats, reset_mxm_cache

    a = _ell(seed=4)
    b = jnp.ones((64, 16), jnp.float32)
    reset_mxm_cache()
    gb.mxm(a, b)  # build plus_times
    gb.mxm(a, b)  # hit
    gb.mxm(a, b, core_sr.MIN_PLUS)  # distinct key: new build, no collision
    s = mxm_cache_stats()
    assert s["builds"] == 2 and s["hits"] == 1, s
    reset_mxm_cache()


def test_mxm_under_jit_falls_back_to_oracle():
    """Tracer operands can't build plans — auto-route must not crash."""
    a = _ell(seed=5)
    b = jnp.ones((64, 8), jnp.float32)

    @jax.jit
    def f(blocks, y):
        w = BlockSparseMatrix(
            blocks, a.col_idx, a.block_mask, a.shape, a.block_shape
        )
        return gb.mxm(w, y)

    np.testing.assert_allclose(
        np.asarray(f(a.blocks, b)),
        np.asarray(gb.mxm(a, b)),
        rtol=1e-6,
    )
