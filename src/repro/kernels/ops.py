"""Jit'd public wrappers around the Pallas kernels.

One wrapper per kernel: ``semiring_matmul`` (dense), ``bsr_spmm``
(ELL grid), ``bcsr_spmm`` (occupancy-exact CSR grid — also fills the
empty block-rows the kernel grid never visits). See the package
docstring for when dispatch picks which.

On TPU the kernels run compiled; everywhere else (this container is
CPU-only) they run in ``interpret=True`` mode, which executes the kernel
body in Python/XLA-CPU for correctness validation. ``auto_interpret()``
makes that decision once.

Wrappers also handle shape padding to the kernel block grid, so callers
can pass arbitrary (m, k, n).

Differentiability: for the arithmetic (``plus_times``) semiring the
sparse wrappers route through the ``jax.custom_vjp`` rules of
``repro.kernels.autodiff`` — ``jax.grad`` through ``bsr_spmm`` /
``bcsr_spmm`` yields sparse-preserving weight cotangents (same layout as
the primal, no densify) and ``Aᵀ·dY`` operand gradients. Other semirings
keep the primal-only kernel path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import DEFAULT_BLOCK_N
from repro.kernels import autodiff as _ad
from repro.kernels import bcsr_spmm as _bcsr
from repro.kernels import bsr_spmm as _bsr
from repro.kernels import semiring_matmul as _smm
from repro.kernels.semirings import kernel_zero
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array


@functools.cache
def auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _semiring_zero(semiring_name: str) -> float:
    """The ⊕-identity used for k-padding and empty-row fills — the same
    registry-derived value the kernels init their accumulators with
    (``repro.kernels.semirings``), so fills and inits cannot drift."""
    return kernel_zero(semiring_name)


def _pad_to(x: Array, axis: int, mult: int, fill: float = 0.0) -> Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


@functools.partial(
    jax.jit,
    static_argnames=(
        "semiring_name",
        "fuse_bias_relu",
        "block_m",
        "block_n",
        "block_k",
        "interpret",
    ),
)
def semiring_matmul(
    a: Array,
    b: Array,
    bias: Array | None = None,
    *,
    semiring_name: str = "plus_times",
    fuse_bias_relu: bool = False,
    block_m: int = DEFAULT_BLOCK_N,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
) -> Array:
    """Padded, jit'd ``C = A ⊕.⊗ B`` (+ optional fused bias/ReLU)."""
    interpret = auto_interpret() if interpret is None else interpret
    m, k = a.shape
    n = b.shape[1]
    block_m = min(block_m, _ceil_mult(m))
    block_n = effective_block_n(n, block_n)
    block_k = min(block_k, _ceil_mult(k))
    sr_zero = _semiring_zero(semiring_name)
    ap = _pad_to(_pad_to(a, 0, block_m), 1, block_k, fill=sr_zero)
    bp = _pad_to(_pad_to(b, 0, block_k, fill=sr_zero), 1, block_n)
    # NOTE: for plus_times zero-padding is exact. For max/min semirings the
    # ⊗ over padded k-entries uses the ⊕-identity so it cannot win the
    # reduction either.
    bias_p = None
    if bias is not None:
        bias_p = _pad_to(bias, 0, block_m)
    out = _smm.semiring_matmul(
        ap,
        bp,
        semiring_name=semiring_name,
        bias=bias_p,
        fuse_bias_relu=fuse_bias_relu,
        block_m=block_m,
        block_n=block_n,
        block_k=block_k,
        interpret=interpret,
    )
    return out[:m, :n]


def _ceil_mult(size: int, base: int = 8) -> int:
    """Largest power-of-two block ≤ DEFAULT_BLOCK_N that keeps padding small."""
    b = DEFAULT_BLOCK_N
    while b > base and size < b:
        b //= 2
    return b


def effective_block_n(n: int, block_n: int = DEFAULT_BLOCK_N) -> int:
    """The column-tile width a wrapper actually runs for an (·, n) panel:
    the requested tile shrunk to the largest power-of-two that keeps the
    pad small — EXACTLY the clamp every wrapper below applies, exposed so
    the cost model (``repro.plan.cost``) and the autotuner
    (``repro.tune``) bill the same grid the kernels execute."""
    return min(block_n, _ceil_mult(n))


@functools.partial(
    jax.jit,
    static_argnames=("semiring_name", "fuse_bias_relu", "block_n", "interpret"),
)
def bsr_spmm(
    a: BlockSparseMatrix,
    b: Array,
    bias: Array | None = None,
    *,
    semiring_name: str = "plus_times",
    fuse_bias_relu: bool = False,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
) -> Array:
    """Padded, jit'd block-sparse ``C = A ⊕.⊗ B`` (+ fused epilogue).

    Differentiable for ``plus_times`` (custom VJP: sparse-preserving
    weight cotangent, occupancy-exact dX — see ``kernels.autodiff``).
    """
    interpret = auto_interpret() if interpret is None else interpret
    n = b.shape[1]
    block_n = effective_block_n(n, block_n)
    bp = _pad_to(b, 1, block_n)
    if fuse_bias_relu and bias is None:
        raise ValueError("fuse_bias_relu requires bias")
    if semiring_name == "plus_times":
        bias_arr = bias if bias is not None else jnp.zeros((a.shape[0],), jnp.float32)
        cfg = _ad.SpmmConfig(fuse_bias_relu, block_n, interpret)
        out = _ad.bsr_spmm_diff(cfg, a, bp, bias_arr)
    else:
        out = _bsr.bsr_spmm(
            a,
            bp,
            semiring_name=semiring_name,
            bias=bias,
            fuse_bias_relu=fuse_bias_relu,
            block_n=block_n,
            interpret=interpret,
        )
    return out[:, :n]


@functools.partial(
    jax.jit,
    static_argnames=("semiring_name", "fuse_bias_relu", "block_n", "interpret"),
)
def bcsr_spmm(
    a: BlockCSRMatrix,
    b: Array,
    bias: Array | None = None,
    transpose_plan=None,
    *,
    semiring_name: str = "plus_times",
    fuse_bias_relu: bool = False,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool | None = None,
) -> Array:
    """Padded, jit'd occupancy-exact block-CSR ``C = A ⊕.⊗ B``.

    Grid steps ∝ stored nnz blocks (vs ``nrb × max_blocks_per_row`` for
    the ELL kernel). Block-rows with no stored blocks are filled with the
    epilogue of the semiring zero here (the kernel never visits them).

    Differentiable for ``plus_times``: the custom VJP runs the backward
    dX = Aᵀ·dY through this same Pallas kernel on the (jittable) block-
    CSR transpose, and the weight cotangent lands only on stored blocks.
    ``transpose_plan`` (``BcsrTransposePlan`` from ``a.transpose_plan()``
    or a ``repro.plan`` StackPlan) removes the backward's per-call
    topology re-sort — the frozen pattern is sorted once, at plan build.
    """
    interpret = auto_interpret() if interpret is None else interpret
    n = b.shape[1]
    block_n = effective_block_n(n, block_n)
    bp = _pad_to(b, 1, block_n)
    if fuse_bias_relu and bias is None:
        raise ValueError("fuse_bias_relu requires bias")
    if semiring_name == "plus_times":
        bias_arr = bias if bias is not None else jnp.zeros((a.shape[0],), jnp.float32)
        cfg = _ad.SpmmConfig(fuse_bias_relu, block_n, interpret)
        out = _ad.bcsr_spmm_diff(cfg, a, bp, bias_arr, transpose_plan)[:, :n]
    else:
        out = _bcsr.bcsr_spmm(
            a,
            bp,
            semiring_name=semiring_name,
            bias=bias,
            fuse_bias_relu=fuse_bias_relu,
            block_n=block_n,
            interpret=interpret,
        )[:, :n]
    # Empty block-rows: kernel grid never maps them — splice in the
    # epilogue of the accumulator init (semiring zero).
    fill = jnp.full((a.shape[0],), _semiring_zero(semiring_name), out.dtype)
    if fuse_bias_relu:
        fill = jnp.maximum(fill + bias.astype(out.dtype), 0).astype(out.dtype)
    counts = a.row_ptr[1:] - a.row_ptr[:-1]
    row_empty = jnp.repeat(
        counts == 0, a.block_shape[0], total_repeat_length=a.shape[0]
    )
    return jnp.where(row_empty[:, None], fill[:, None], out)

