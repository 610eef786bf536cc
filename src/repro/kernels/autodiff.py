"""Custom VJP rules that make the sparse Pallas kernels trainable.

The paper's equations (y = h(Wᵀx + b)) cover training as well as
inference, and the Graph Challenge studies (arXiv:1909.05631,
arXiv:2004.01181) show sparse-times-dense products dominate BOTH passes.
This module closes the loop: ``jax.custom_vjp`` rules for the two SpMM
kernels so ``jax.grad`` / ``jax.value_and_grad`` flow through them with
**no densification anywhere**:

  primal      Z = A ⊕.⊗ B (+ fused ``max(Z + b·1ᵀ, 0)`` epilogue)
  dB  (dense) = Aᵀ · dZ          — occupancy-exact transpose product
  dA  (sparse) at stored block positions ONLY:
                dA[blk] = dZ_row(blk) · Bᵀ_col(blk)
                (the sampled/SDDMM-style product; same ELL or CSR layout
                 as the primal, padded/invalid slots exactly zero)
  db  (bias)  = Σₙ dZ  (masked by the ReLU when the epilogue is fused)

Backward-pass routing:

  * ``bcsr`` — dB runs through the **Pallas CSR kernel itself** on the
    block-CSR transpose (fully jittable because ``total_blocks`` is
    static), so the backward hot path is kernel-resident like the
    forward. dA uses the jnp sampled product
    (``sparse.ops.bcsr_weight_cotangent``). The transpose's argsort is
    the only per-call analysis left: pass a cached
    :class:`~repro.sparse.bcsr.BcsrTransposePlan` (built once per
    topology by ``repro.plan`` / ``BlockCSRMatrix.transpose_plan``) and
    the backward re-sorts NOTHING — it gathers fresh values through the
    cached permutation instead.
  * ``bsr/ELL`` — the ELL transpose needs a static output pad width that
    a traced weight cannot provide, so dB uses the occupancy-exact
    scatter-⊕ (``sparse.ops.bsr_transpose_matmul``) and dA the sampled
    product; both scale with stored blocks, neither densifies.

Only the arithmetic (``plus_times``) semiring is differentiable — ReLU
is the fused max-plus step and its subgradient is handled here; the
exotic semirings keep the primal-only kernel path
(``repro.kernels.ops`` dispatches).

Cotangent structure: the sparse weight's cotangent is a
:class:`BlockSparseMatrix` / :class:`BlockCSRMatrix` whose float leaves
carry the gradient and whose integer/bool topology leaves carry the
``float0`` zeros JAX expects for non-differentiable leaves — optimizers
that guard on param dtype (``repro.train.optimizer``) consume it as-is.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import dtypes as jax_dtypes

from repro.kernels import DEFAULT_BLOCK_N
from repro.kernels import bcsr_spmm as _bcsr
from repro.kernels import bsr_spmm as _bsr
from repro.sparse import ops as sparse_ops
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array


class SpmmConfig(NamedTuple):
    """Static (hashable) kernel-call configuration threaded through the
    custom_vjp as a nondiff argument."""

    fuse_bias_relu: bool
    block_n: int = DEFAULT_BLOCK_N
    interpret: bool = False


def _float0_zeros(x) -> np.ndarray:
    """The cotangent JAX expects for integer/bool primal leaves."""
    return np.zeros(np.shape(x), jax_dtypes.float0)


def _relu_mask_and_bias_grad(cfg: SpmmConfig, out: Array, g: Array, bias):
    """Shared epilogue backward: push g through the fused max(·+b, 0)."""
    g = g.astype(jnp.float32)
    if cfg.fuse_bias_relu:
        dz = jnp.where(out > 0, g, 0.0)
        dbias = jnp.sum(dz, axis=1).astype(bias.dtype)
    else:
        dz = g
        dbias = jnp.zeros_like(bias)
    return dz, dbias


# --------------------------------------------------------------------------
# ELL-padded BSR kernel
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def bsr_spmm_diff(cfg: SpmmConfig, a: BlockSparseMatrix, b: Array, bias: Array):
    """Differentiable ``bsr_spmm`` (plus_times). ``b.shape[1]`` must be a
    multiple of ``cfg.block_n`` (the jit wrapper in ``kernels.ops`` pads)."""
    return _bsr.bsr_spmm(
        a,
        b,
        semiring_name="plus_times",
        bias=bias,
        fuse_bias_relu=cfg.fuse_bias_relu,
        block_n=cfg.block_n,
        interpret=cfg.interpret,
    )


def _bsr_fwd(cfg, a, b, bias):
    out = bsr_spmm_diff(cfg, a, b, bias)
    return out, (a, b, bias, out)


def _bsr_bwd(cfg, res, g):
    a, b, bias, out = res
    dz, dbias = _relu_mask_and_bias_grad(cfg, out, g, bias)
    # dB = Aᵀ·dZ — occupancy-exact scatter-⊕ (the ELL transpose's pad
    # width is data-dependent, so the jnp path is the jittable one here).
    db = sparse_ops.bsr_transpose_matmul(a, dz).astype(b.dtype)
    # dA only at stored positions — primal's sparsity pattern preserved.
    dblocks = sparse_ops.bsr_weight_cotangent(a, dz, b).astype(a.blocks.dtype)
    da = BlockSparseMatrix(
        dblocks,
        _float0_zeros(a.col_idx),
        _float0_zeros(a.block_mask),
        a.shape,
        a.block_shape,
    )
    return da, db, dbias


bsr_spmm_diff.defvjp(_bsr_fwd, _bsr_bwd)


# --------------------------------------------------------------------------
# Occupancy-exact block-CSR kernel
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def bcsr_spmm_diff(
    cfg: SpmmConfig,
    a: BlockCSRMatrix,
    b: Array,
    bias: Array,
    transpose_plan=None,
):
    """Differentiable ``bcsr_spmm`` (plus_times). Same raw-kernel caveat
    as the primal: empty block-rows are left unwritten — the ``kernels.
    ops`` wrapper splices the fill in OUTSIDE this rule (so upstream
    cotangents for empty rows arrive here already zeroed by the
    ``where``'s own VJP, and the garbage rows can never leak).

    ``transpose_plan`` (a :class:`~repro.sparse.bcsr.BcsrTransposePlan`
    or None) only feeds the backward pass: with it, dB's transpose is a
    gather through the cached permutation; without it, every backward
    re-sorts the (frozen) topology."""
    del transpose_plan  # primal never needs it
    return _bcsr.bcsr_spmm(
        a,
        b,
        semiring_name="plus_times",
        bias=bias,
        fuse_bias_relu=cfg.fuse_bias_relu,
        block_n=cfg.block_n,
        interpret=cfg.interpret,
    )


def _bcsr_fwd(cfg, a, b, bias, transpose_plan):
    out = bcsr_spmm_diff(cfg, a, b, bias, transpose_plan)
    return out, (a, b, bias, out, transpose_plan)


def _bcsr_bwd(cfg, res, g):
    a, b, bias, out, tp = res
    dz, dbias = _relu_mask_and_bias_grad(cfg, out, g, bias)
    # dB = Aᵀ·dZ through the Pallas kernel itself: the block-CSR
    # transpose is fully jittable (static total_blocks), so the backward
    # pass stays on the occupancy-exact kernel grid (∝ true nnz). With a
    # cached plan the per-call argsort disappears entirely — the frozen
    # topology was sorted once, at plan-build time.
    at = a.transpose() if tp is None else tp.apply(a)
    db_raw = _bcsr.bcsr_spmm(
        at,
        dz,
        semiring_name="plus_times",
        block_n=cfg.block_n,
        interpret=cfg.interpret,
    )
    # Rows of Aᵀ with no stored blocks (= empty columns of A) are never
    # visited by the kernel grid → their dB rows are identically zero.
    empty_t = (at.row_ptr[1:] == at.row_ptr[:-1])
    row_empty = jnp.repeat(
        empty_t, at.block_shape[0], total_repeat_length=at.shape[0]
    )
    db = jnp.where(row_empty[:, None], 0.0, db_raw).astype(b.dtype)
    # dA: sampled products at the stored blocks, CSR order preserved.
    dvalues = sparse_ops.bcsr_weight_cotangent(a, dz, b).astype(a.values.dtype)
    da = BlockCSRMatrix(
        dvalues,
        _float0_zeros(a.row_ptr),
        _float0_zeros(a.row_id),
        _float0_zeros(a.col_idx),
        _float0_zeros(a.valid),
        a.shape,
        a.block_shape,
    )
    # The plan is pure frozen topology (int/bool leaves) — its cotangent
    # is the float0 pytree JAX expects for non-differentiable leaves.
    dtp = None
    if tp is not None:
        from repro.sparse.bcsr import BcsrTransposePlan

        dtp = BcsrTransposePlan(
            _float0_zeros(tp.order),
            _float0_zeros(tp.row_ptr),
            _float0_zeros(tp.row_id),
            _float0_zeros(tp.col_idx),
            _float0_zeros(tp.valid),
            tp.shape,
            tp.block_shape,
        )
    return da, db, dbias, dtp


bcsr_spmm_diff.defvjp(_bcsr_fwd, _bcsr_bwd)

