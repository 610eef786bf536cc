"""Occupancy-exact block-CSR × dense Pallas TPU kernel.

The ELL kernel (``bsr_spmm``) runs a ``(nrb, n_tiles, max_blocks_per_row)``
grid: wall-clock scales with the *worst-case* row occupancy because
padded slots still cost a grid step and the B-panel HBM→VMEM DMA even
though ``pl.when`` skips their compute. This kernel's grid is

    (n_tiles, total_nnz_blocks)

— one step per *stored* block, so compute AND DMA traffic scale with
true nnz (the paper's §V claim carried into the grid). The CSR row map
(``row_id``) is scalar-prefetched into SMEM and drives both the output
BlockSpec ``index_map`` and the accumulator lifecycle:

  * a step whose ``row_id`` differs from the previous step's opens a new
    output row-block → re-init the VMEM accumulator;
  * a step whose ``row_id`` differs from the *next* step's closes the
    row → apply the (optional) fused ``max(acc + bias, 0)`` epilogue and
    store; Pallas' revisiting machinery flushes the tile to HBM when the
    mapped output block changes.

Block-rows with no stored blocks are never visited; the host wrapper
(``repro.kernels.ops.bcsr_spmm``) fills them with the epilogue of the
semiring zero, matching the oracle's masked semantics.

Semirings: the full ``core/semiring.py`` registry — ``plus_times`` on
the MXU, everything else on the VPU via the registry-derived dispatch
in ``repro.kernels.semirings`` (⊕-identity accumulator init on every
row *open*, so the flush-on-row-change protocol is correct for
non-additive monoids; invalid slots are skipped before they can touch
the accumulator, which is what annihilator-aware padding means here).

Autodiff: this module is the primal only. The ``plus_times`` form is
made differentiable by the ``jax.custom_vjp`` rule in
``repro.kernels.autodiff`` (attached at the ``repro.kernels.ops``
wrapper); notably its backward dX = Wᵀ·dY re-enters THIS kernel on the
device-side ``BlockCSRMatrix.transpose()`` (fully jittable — static
``total_blocks``), so the backward pass also runs on the
occupancy-exact grid. The weight cotangent lands only on stored blocks
(invalid tail slots exactly zero). See docs/kernels.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import DEFAULT_BLOCK_N, prefetch_fits

from repro.kernels.semirings import accumulate_tile, kernel_semiring
from repro.sparse.bcsr import BlockCSRMatrix

Array = jax.Array


def grid_steps(a: BlockCSRMatrix, n: int, block_n: int = DEFAULT_BLOCK_N) -> int:
    """Grid steps this kernel executes — ∝ stored blocks, not the ELL pad."""
    return a.total_blocks * -(-n // block_n)


def smem_fits(a: BlockCSRMatrix) -> bool:
    """Do the three (T,) prefetch tables (row, column, valid) fit SMEM?
    That caps one call at 86016 stored blocks."""
    return prefetch_fits(*[(a.total_blocks,)] * 3)


def _kernel(
    row_id_ref,  # scalar-prefetch (T,) int32
    col_idx_ref,  # scalar-prefetch (T,) int32 (drives the B BlockSpec)
    valid_ref,  # scalar-prefetch (T,) int32
    values_ref,  # (1, bs_r, bs_c)
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
    t = pl.program_id(1)
    row = row_id_ref[t]
    prev_row = row_id_ref[jnp.maximum(t - 1, 0)]
    next_row = row_id_ref[jnp.minimum(t + 1, t_steps - 1)]
    row_opens = (t == 0) | (row != prev_row)
    row_closes = (t == t_steps - 1) | (row != next_row)

    @pl.when(row_opens)
    def _init():
        # ⊕-identity init on every row OPEN — the flush-on-row-change
        # lifecycle stays correct for non-additive monoids because a
        # fresh row never sees another row's partial.
        acc_ref[...] = jnp.full_like(acc_ref, spec.init)

    @pl.when(valid_ref[t] != 0)
    def _accumulate():
        # invalid tail slots never reach the accumulator: skipped work
        # contributes exactly the ⊕-identity (annihilator-aware padding)
        a = values_ref[0].astype(jnp.float32)
        b = b_ref[...].astype(jnp.float32)
        acc_ref[...] = accumulate_tile(spec, a, b, acc_ref[...])

    @pl.when(row_closes)
    def _epilogue():
        acc = acc_ref[...]
        if fuse_bias_relu:
            acc = jnp.maximum(acc + bias_ref[...].astype(jnp.float32), 0.0)
        o_ref[...] = acc.astype(o_ref.dtype)


def bcsr_spmm(
    a: BlockCSRMatrix,
    b: Array,
    *,
    semiring_name: str = "plus_times",
    bias: Array | None = None,
    fuse_bias_relu: bool = False,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
    out_dtype=None,
) -> Array:
    """C (m, n) = A ⊕.⊗ B for block-CSR A (m, k), dense B (k, n).

    Block-rows of A with zero stored blocks are left UNWRITTEN in the
    output — callers must mask them (``repro.kernels.ops.bcsr_spmm``
    does). n must divide ``block_n``.
    """
    m, k = a.shape
    assert b.shape[0] == k, (a.shape, b.shape)
    n = b.shape[1]
    bs_r, bs_c = a.block_shape
    t_steps = a.total_blocks
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
        t_steps=t_steps,
        fuse_bias_relu=fuse_bias_relu,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # j outer / t inner: each output column stripe walks the stored
        # blocks once, in CSR order, flushing on row change.
        grid=(n // block_n, t_steps),
        in_specs=[
            # stored block t
            pl.BlockSpec((1, bs_r, bs_c), lambda j, t, ri, ci, vd: (t, 0, 0)),
            # B panel selected by the prefetched block-column index
            pl.BlockSpec((bs_c, block_n), lambda j, t, ri, ci, vd: (ci[t], j)),
            # bias row-tile of the block's row
            pl.BlockSpec((bs_r, 1), lambda j, t, ri, ci, vd: (ri[t], 0)),
        ],
        out_specs=pl.BlockSpec(
            (bs_r, block_n), lambda j, t, ri, ci, vd: (ri[t], j)
        ),
        scratch_shapes=[pltpu.VMEM((bs_r, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        name="bcsr_spmm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        a.row_id,
        a.col_idx,
        a.valid.astype(jnp.int32),
        a.values,
        b,
        bias2d,
    )
