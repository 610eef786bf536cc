"""Block-sparse (ELL-padded BSR) × dense Pallas TPU kernel.

The TPU-native port of the paper's CSR SpMM (DESIGN.md §2): each stored
nonzero *block* becomes one dense MXU matmul; the block-column index table
is scalar-prefetched into SMEM and drives the B-panel gather via the
BlockSpec ``index_map`` (so the HBM→VMEM DMA only ever touches B panels
that are actually needed — compute AND bandwidth scale with nnz blocks).

grid = (row_blocks, n_tiles, max_blocks_per_row):
  t-axis walks the stored blocks of row-block i; the (i, j) output tile
  accumulates in VMEM scratch; invalid (padding) slots are skipped via
  ``block_mask`` + ``pl.when``. The final t-step applies the optional
  fused max-plus epilogue  max(acc + bias, 0)  — the paper's eWiseMult +
  eWiseAdd collapsed into the matmul's last store.

Semirings: the full ``core/semiring.py`` registry — ``plus_times`` on
the MXU, everything else chunked on the VPU via the registry-derived
dispatch in ``repro.kernels.semirings`` (⊕-identity accumulator init at
``t == 0``; masked pad slots are skipped before they can touch the
accumulator, so padding contributes exactly the ⊕-identity).

Autodiff: this module is the primal only. The ``plus_times`` form is
made differentiable by the ``jax.custom_vjp`` rule in
``repro.kernels.autodiff`` (attached at the ``repro.kernels.ops``
wrapper): dX = Wᵀ·dY via the occupancy-exact scatter-⊕ and a weight
cotangent computed only at stored (mask-true) block slots — same ELL
layout as the primal, padded slots exactly zero. See docs/kernels.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import DEFAULT_BLOCK_N, prefetch_fits

from repro.kernels.semirings import accumulate_tile, kernel_semiring
from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array


def grid_steps(a: BlockSparseMatrix, n: int, block_n: int = DEFAULT_BLOCK_N) -> int:
    """Grid steps this kernel executes — the ELL pad is billed in full
    (``nrb × max_blocks_per_row`` per column tile), read from the
    weight's own layout."""
    nrb, mbpr = a.col_idx.shape
    return nrb * mbpr * (-(-n // block_n))


def smem_fits(a: BlockSparseMatrix) -> bool:
    """Do the (nrb, mbpr) column table and mask fit SMEM? Each pads to
    (8, 128) tiles there, so with mbpr ≤ 128 every block-row costs
    1 KiB: the ELL kernel stops at 1008 block-rows (16128 rows at
    block 16)."""
    return prefetch_fits(a.col_idx.shape, a.col_idx.shape)


def _kernel(
    col_idx_ref,  # scalar-prefetch (nrb, mbpr) int32
    mask_ref,  # scalar-prefetch (nrb, mbpr) int32
    blocks_ref,  # (1, 1, bs_r, bs_c)
    b_ref,  # (bs_c, bn)
    bias_ref,  # (bs_r, 1)
    o_ref,  # (bs_r, bn)
    acc_ref,  # VMEM scratch (bs_r, bn) f32
    *,
    semiring_name: str,
    t_steps: int,
    fuse_bias_relu: bool,
):
    spec = kernel_semiring(semiring_name)
    i = pl.program_id(0)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, spec.init)

    @pl.when(mask_ref[i, t] != 0)
    def _accumulate():
        # masked ELL pad slots never reach the accumulator: skipped work
        # contributes exactly the ⊕-identity (annihilator-aware padding)
        a = blocks_ref[0, 0].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        acc_ref[...] = accumulate_tile(spec, a, b, acc_ref[...])

    @pl.when(t == t_steps - 1)
    def _epilogue():
        acc = acc_ref[...]
        if fuse_bias_relu:
            acc = jnp.maximum(acc + bias_ref[...].astype(jnp.float32), 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


def bsr_spmm(
    a: BlockSparseMatrix,
    b: Array,
    *,
    semiring_name: str = "plus_times",
    bias: Array | None = None,
    fuse_bias_relu: bool = False,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
    out_dtype=None,
) -> Array:
    """C (m, n) = A ⊕.⊗ B for ELL-padded BSR A (m, k), dense B (k, n)."""
    m, k = a.shape
    assert b.shape[0] == k, (a.shape, b.shape)
    n = b.shape[1]
    bs_r, bs_c = a.block_shape
    nrb, mbpr = a.col_idx.shape
    assert n % block_n == 0, (n, block_n)
    if fuse_bias_relu and bias is None:
        raise ValueError("fuse_bias_relu requires bias")
    kernel_semiring(semiring_name)  # fail fast on unknown semirings
    if bias is None:
        bias = jnp.zeros((m,), jnp.float32)
    bias2d = bias[:, None]
    out_dtype = out_dtype or jnp.result_type(a.dtype, b.dtype)

    kernel = functools.partial(
        _kernel,
        semiring_name=semiring_name,
        t_steps=mbpr,
        fuse_bias_relu=fuse_bias_relu,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nrb, n // block_n, mbpr),
        in_specs=[
            # stored block (i, t)
            pl.BlockSpec(
                (1, 1, bs_r, bs_c), lambda i, j, t, ci, mk: (i, t, 0, 0)
            ),
            # B panel selected by the scalar-prefetched block-column index
            pl.BlockSpec((bs_c, block_n), lambda i, j, t, ci, mk: (ci[i, t], j)),
            # bias row-tile
            pl.BlockSpec((bs_r, 1), lambda i, j, t, ci, mk: (i, 0)),
        ],
        out_specs=pl.BlockSpec(
            (bs_r, block_n), lambda i, j, t, ci, mk: (i, j)
        ),
        scratch_shapes=[pltpu.VMEM((bs_r, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        a.col_idx,
        a.block_mask.astype(jnp.int32),
        a.blocks,
        b,
        bias2d,
    )
