"""VMEM-resident multi-layer fused forward Pallas TPU kernel.

``dnn_forward`` re-streams the (m, n) activation panel through HBM once
per layer: L layers → L−1 needless round-trips. The GraphChallenge
winners (arXiv:2004.01181, arXiv:1909.05631) fuse the whole layer stack;
this kernel does the TPU equivalent for the paper's square deep MLP
(homogeneous ``stack_bsr`` weight stacks):

  ONE ``pallas_call``, grid = (n_tiles, L, nrb, max_blocks_per_row).

Per output column stripe j, the full (m, block_n) activation panel lives
in a double-buffered VMEM scratch: layer l reads panel ``l % 2`` and
writes ``(l+1) % 2`` row-block by row-block, applying the per-layer
``max(W·Y + b, 0)`` epilogue in-register. Only y0 is read from HBM and
only Y[L] is written back.

VMEM budget: the 2 ping-pong panels + the y0 and out stripes, each
double-buffered by the Pallas pipeline — 6 (m, block_n) stripes — plus
small per-step tiles. Callers check :func:`fused_mlp_vmem_bytes` before
dispatching (``repro.plan.routes`` falls back to the tiled or layered
route when the panel would not fit).

Scalar prefetch: both fused kernels take ONE flat int32 slot table of
L·nrb·mbpr entries — the block column, or -1 for an ELL pad slot —
because SMEM is 1 MiB and Mosaic pads a 3-D (L, nrb, mbpr) table to
(8, 128) tiles. :func:`fused_prefetch_fits` is the route layer's check.

Weights use the ELL layout (the stack shares one static
``max_blocks_per_row``); the occupancy-exact CSR grid and the resident
panel are complementary optimisations — CSR wins on skewed single
layers, residency wins on deep stacks — and dispatch picks per workload.

plus_times only: the per-layer ReLU epilogue is the paper's max-plus
step already fused in; other semirings take the layered path.

Forward-only: per-layer activations never exist outside VMEM, so there
is nothing to checkpoint for a backward pass — ``jax.grad`` through the
``repro.kernels.ops`` wrapper raises ``NotImplementedError`` (rule in
``repro.kernels.autodiff``) pointing at the layered differentiable path
(``core.dnn.dnn_forward_trainable``); ``serve.SparseDNNEngine(
differentiable=True)`` routes around this kernel automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import DEFAULT_BLOCK_N, prefetch_fits
from repro.kernels.semirings import mxu_dot

from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array

# Mosaic's default scoped-VMEM limit on v5e. The resident stripes get
# VMEM_SOFT_LIMIT_BYTES of it; the rest holds the per-step weight, bias
# and accumulator tiles.
VMEM_SCOPED_LIMIT_BYTES = 16 * 1024 * 1024
VMEM_SOFT_LIMIT_BYTES = 12 * 1024 * 1024


def _panel_np_dtype(panel_dtype) -> np.dtype:
    """Canonical activation-panel dtype: f32 unless the caller opts into
    a reduced-precision panel (name, np/jnp dtype — all accepted)."""
    return np.dtype(panel_dtype if panel_dtype is not None else np.float32)


def fused_mlp_vmem_bytes(
    m: int, block_n: int = DEFAULT_BLOCK_N, panel_dtype=None
) -> int:
    """VMEM bytes of the resident kernel's (m, block_n) stripes, as
    the compiler allocates them: the ping-pong ybuf pair, plus the y0
    and out stripes, which the Pallas pipeline double-buffers.

    All six are held in ``panel_dtype``, so bf16 panels halve this bill
    and move the resident↔tiled boundary (accumulation stays f32 in a
    block-sized register tile)."""
    panel = m * block_n * _panel_np_dtype(panel_dtype).itemsize
    return 6 * panel  # ybuf×2 + y0 stripe×2 + out stripe×2


def fused_mlp_eligible(
    w: BlockSparseMatrix,
    block_n: int = DEFAULT_BLOCK_N,
    *,
    panel_dtype=None,
    vmem_limit: int | None = None,
) -> bool:
    """Square stack small enough for the panel to live in VMEM."""
    m, k = w.shape
    limit = VMEM_SOFT_LIMIT_BYTES if vmem_limit is None else vmem_limit
    return m == k and fused_mlp_vmem_bytes(m, block_n, panel_dtype) <= limit


def fused_prefetch_fits(w: BlockSparseMatrix, n_layers: int) -> bool:
    """Does an ``n_layers`` stack of ``w``-shaped layers fit the fused
    kernels' flat slot table into SMEM?"""
    nrb, mbpr = w.col_idx.shape[-2:]
    return prefetch_fits((n_layers * nrb * mbpr,))


def _slot_table(stacked_w: BlockSparseMatrix) -> Array:
    """The flat (L·nrb·mbpr,) prefetch: block column, -1 on pad slots."""
    valid = stacked_w.block_mask.astype(bool)
    return jnp.where(valid, stacked_w.col_idx, -1).astype(jnp.int32).reshape(-1)


def fused_mlp_tiled_eligible(
    w: BlockSparseMatrix, block_n: int = DEFAULT_BLOCK_N
) -> bool:
    """Square stack of ANY height — the tiled variant keeps the panel in
    HBM and holds only per-block tiles in VMEM, so there is no
    panel-size ceiling (the slot table's SMEM bill is checked apart,
    by :func:`fused_prefetch_fits`). (Dispatch still prefers the fully resident kernel
    whenever :func:`fused_mlp_eligible` says the panel fits.)"""
    m, k = w.shape
    return m == k


def _kernel(
    slot_ref,  # scalar-prefetch (L·nrb·mbpr,) int32 block column, -1 = pad
    blocks_ref,  # (1, 1, 1, bs_r, bs_c)
    y0_ref,  # (m, bn) — this j-stripe of the input panel
    bias_ref,  # (1, bs_r, 1)
    o_ref,  # (m, bn) — this j-stripe of Y[L]
    ybuf_ref,  # VMEM scratch (2, m, bn) panel_dtype double-buffered panel
    acc_ref,  # VMEM scratch (bs_r, bn) f32
    *,
    n_layers: int,
    n_row_blocks: int,
    t_steps: int,
    bs_r: int,
    bs_c: int,
    panel_dtype,
):
    l = pl.program_id(1)
    i = pl.program_id(2)
    t = pl.program_id(3)
    c = slot_ref[(l * n_row_blocks + i) * t_steps + t]

    @pl.when((l == 0) & (i == 0) & (t == 0))
    def _load_input_panel():
        ybuf_ref[0] = y0_ref[...].astype(panel_dtype)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(c >= 0)
    def _accumulate():
        w = blocks_ref[0, 0, 0].astype(jnp.float32)
        y = ybuf_ref[l % 2, pl.ds(c * bs_c, bs_c), :]
        acc_ref[...] += mxu_dot(w, y)

    @pl.when(t == t_steps - 1)
    def _close_row_block():
        # The paper's eWiseMult(+bias) / eWiseAdd(max 0) pair, in-register.
        val = jnp.maximum(acc_ref[...] + bias_ref[0].astype(jnp.float32), 0.0)
        ybuf_ref[(l + 1) % 2, pl.ds(i * bs_r, bs_r), :] = val.astype(panel_dtype)

        @pl.when(l == n_layers - 1)
        def _store_output():
            o_ref[pl.ds(i * bs_r, bs_r), :] = val.astype(o_ref.dtype)


def fused_mlp_forward(
    stacked_w: BlockSparseMatrix,
    stacked_b: Array,
    y0: Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
    out_dtype=None,
    panel_dtype=None,
) -> Array:
    """Y[L] (m, n) = relu-MLP(y0) through all L layers in one kernel.

    ``stacked_w.blocks``: (L, nrb, mbpr, bs_r, bs_c) — a ``stack_bsr``
    result; ``stacked_b``: (L, m). Requires square layers (m == k) and
    ``n % block_n == 0``. ``panel_dtype=jnp.bfloat16`` keeps every
    activation stripe (ybuf pair, y0, out) in bf16 — halving
    :func:`fused_mlp_vmem_bytes` — while the per-block accumulate and the
    bias/ReLU epilogue stay f32; the result is cast back to
    ``out_dtype``.
    """
    m, k = stacked_w.shape
    if m != k:
        raise ValueError(f"fused MLP needs square layers, got {stacked_w.shape}")
    if stacked_w.blocks.ndim != 5:
        raise ValueError("stacked_w must carry a leading L axis (stack_bsr)")
    n_layers, nrb, mbpr = stacked_w.col_idx.shape
    bs_r, bs_c = stacked_w.block_shape
    n = y0.shape[1]
    assert y0.shape[0] == k, (stacked_w.shape, y0.shape)
    assert n % block_n == 0, (n, block_n)
    assert stacked_b.shape == (n_layers, m), stacked_b.shape
    out_dtype = out_dtype or jnp.result_type(stacked_w.dtype, y0.dtype)
    pdt = _panel_np_dtype(panel_dtype)
    default_panels = pdt == np.dtype(np.float32)

    kernel = functools.partial(
        _kernel,
        n_layers=n_layers,
        n_row_blocks=nrb,
        t_steps=mbpr,
        bs_r=bs_r,
        bs_c=bs_c,
        panel_dtype=pdt,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, n_layers, nrb, mbpr),
        in_specs=[
            # stored block (l, i, t)
            pl.BlockSpec(
                (1, 1, 1, bs_r, bs_c),
                lambda j, l, i, t, sl: (l, i, t, 0, 0),
            ),
            # the full input column stripe for this j
            pl.BlockSpec((m, block_n), lambda j, l, i, t, sl: (0, j)),
            # bias row-tile of layer l, row-block i
            pl.BlockSpec((1, bs_r, 1), lambda j, l, i, t, sl: (l, i, 0)),
        ],
        # the full output column stripe — written once per j, on layer L-1
        out_specs=pl.BlockSpec((m, block_n), lambda j, l, i, t, sl: (0, j)),
        scratch_shapes=[
            pltpu.VMEM((2, m, block_n), pdt),
            pltpu.VMEM((bs_r, block_n), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        # bf16 panels: the streamed y0/out stripes are bf16 too (that is
        # what makes the VMEM bill exactly 6 panels × itemsize); the
        # wrapper casts back to out_dtype below.
        out_shape=jax.ShapeDtypeStruct(
            (m, n), out_dtype if default_panels else pdt
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
            # a tuned budget past the soft limit asks the compiler for it
            vmem_limit_bytes=max(
                VMEM_SCOPED_LIMIT_BYTES,
                fused_mlp_vmem_bytes(m, block_n, pdt)
                + VMEM_SCOPED_LIMIT_BYTES
                - VMEM_SOFT_LIMIT_BYTES,
            ),
        ),
        interpret=interpret,
    )(
        _slot_table(stacked_w),
        stacked_w.blocks,
        y0 if default_panels else y0.astype(pdt),
        stacked_b[:, :, None],
    )
    return out if default_panels else out.astype(out_dtype)


# --------------------------------------------------------------------------
# Multi-panel tiled variant: m beyond the VMEM budget, panel in HBM
# --------------------------------------------------------------------------


def _tiled_kernel(
    slot_ref,  # scalar-prefetch (L·nrb·mbpr,) int32 block column, -1 = pad
    blocks_ref,  # (1, 1, mbpr, bs_r, bs_c) — row-block i's stored blocks
    y0_ref,  # full (m, n) panel_dtype, HBM (never pulled into VMEM whole)
    bias_ref,  # (1, bs_r, 1)
    o_ref,  # full (m, n) panel_dtype, HBM
    panel_ref,  # HBM (2, m, bn) panel_dtype ping-pong activation panel
    ybuf_ref,  # VMEM scratch (2, bs_c, bn) panel_dtype double-buffered gather
    acc_ref,  # VMEM scratch (bs_r, bn) f32
    vout_ref,  # VMEM scratch (bs_r, bn) panel_dtype outgoing row-block stage
    stage_sem,  # DMA semaphore: y0 stripe → panel[0]
    gather_sems,  # DMA semaphores (2,): panel → ybuf slots
    out_sem,  # DMA semaphore: vout → panel/output
    *,
    n_layers: int,
    n_row_blocks: int,
    t_steps: int,
    bs_r: int,
    bs_c: int,
    block_n: int,
):
    j = pl.program_id(0)
    l = pl.program_id(1)
    i = pl.program_id(2)
    src = l % 2  # panel slot layer l reads; (l+1)%2 == 1-src is written
    row = (l * n_row_blocks + i) * t_steps

    @pl.when((l == 0) & (i == 0))
    def _stage_input_stripe():
        # HBM→HBM: this j-stripe of y0 becomes layer 0's input panel.
        cp = pltpu.make_async_copy(
            y0_ref.at[:, pl.ds(j * block_n, block_n)],
            panel_ref.at[0],
            stage_sem,
        )
        cp.start()
        cp.wait()

    def gather(t, slot):
        c = jnp.maximum(slot_ref[row + t], 0)  # pad slots gather block 0
        return pltpu.make_async_copy(
            panel_ref.at[src, pl.ds(c * bs_c, bs_c), :],
            ybuf_ref.at[slot],
            gather_sems.at[slot],
        )

    gather(0, 0).start()
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(t, carry):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < t_steps)
        def _prefetch_next():
            gather(t + 1, jax.lax.rem(t + 1, 2)).start()

        gather(t, slot).wait()

        @pl.when(slot_ref[row + t] >= 0)
        def _accumulate():
            w = blocks_ref[0, 0, t].astype(jnp.float32)
            acc_ref[...] += mxu_dot(w, ybuf_ref[slot])

        return carry

    jax.lax.fori_loop(0, t_steps, body, 0)

    # Same in-register epilogue as the resident kernel, then one DMA to
    # the next layer's panel slot (waited: layer l+1 may read ANY block).
    vout_ref[...] = jnp.maximum(
        acc_ref[...] + bias_ref[0].astype(jnp.float32), 0.0
    ).astype(vout_ref.dtype)
    cp = pltpu.make_async_copy(
        vout_ref,
        panel_ref.at[1 - src, pl.ds(i * bs_r, bs_r), :],
        out_sem,
    )
    cp.start()
    cp.wait()

    @pl.when(l == n_layers - 1)
    def _store_output():
        cp2 = pltpu.make_async_copy(
            vout_ref,
            o_ref.at[pl.ds(i * bs_r, bs_r), pl.ds(j * block_n, block_n)],
            out_sem,
        )
        cp2.start()
        cp2.wait()


def fused_mlp_tiled_forward(
    stacked_w: BlockSparseMatrix,
    stacked_b: Array,
    y0: Array,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
    out_dtype=None,
    panel_dtype=None,
) -> Array:
    """Y[L] = relu-MLP(y0), ONE ``pallas_call``, panel tiled over m.

    The resident kernel's (2, m, block_n) VMEM scratch caps m at
    ``VMEM_SOFT_LIMIT_BYTES``; past it this variant keeps the ping-pong
    activation panel in **HBM** (an extra kernel output the wrapper
    drops) and tiles the m dimension over
    the row-block grid: grid = (n_tiles, L, nrb) — each step DMAs the
    row's ≤ ``max_blocks_per_row`` input blocks into a double-buffered
    (bs_c, block_n) VMEM window (overlapping the gather of block t+1
    with the MXU product of block t), closes the row with the fused
    ``max(W·Y+b, 0)`` epilogue, and DMAs the (bs_r, block_n) result to
    the next layer's panel slot. VMEM use is O(mbpr·bs² + bs·block_n) —
    independent of m — while the stack still runs as a single kernel
    with no per-layer XLA round-trips (the GraphChallenge 16k/64k-neuron
    configs land here).

    Same contract as :func:`fused_mlp_forward` otherwise: homogeneous
    square ``stack_bsr`` stacks, ``n % block_n == 0``, forward-only.
    """
    m, k = stacked_w.shape
    if m != k:
        raise ValueError(f"fused MLP needs square layers, got {stacked_w.shape}")
    if stacked_w.blocks.ndim != 5:
        raise ValueError("stacked_w must carry a leading L axis (stack_bsr)")
    n_layers, nrb, mbpr = stacked_w.col_idx.shape
    bs_r, bs_c = stacked_w.block_shape
    n = y0.shape[1]
    assert y0.shape[0] == k, (stacked_w.shape, y0.shape)
    assert n % block_n == 0, (n, block_n)
    assert stacked_b.shape == (n_layers, m), stacked_b.shape
    out_dtype = out_dtype or jnp.result_type(stacked_w.dtype, y0.dtype)
    pdt = _panel_np_dtype(panel_dtype)

    kernel = functools.partial(
        _tiled_kernel,
        n_layers=n_layers,
        n_row_blocks=nrb,
        t_steps=mbpr,
        bs_r=bs_r,
        bs_c=bs_c,
        block_n=block_n,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block_n, n_layers, nrb),
        in_specs=[
            # all stored blocks of (layer l, row-block i)
            pl.BlockSpec(
                (1, 1, mbpr, bs_r, bs_c),
                lambda j, l, i, sl: (l, i, 0, 0, 0),
            ),
            # the input panel stays in HBM; the kernel DMAs slices
            pl.BlockSpec(memory_space=pl.ANY),
            # bias row-tile of layer l, row-block i
            pl.BlockSpec((1, bs_r, 1), lambda j, l, i, sl: (l, i, 0)),
        ],
        # Y[L], plus the ping-pong activation panel. Mosaic allocates
        # scratch only in VMEM/SMEM, so the HBM panel is a second output
        # that the wrapper drops.
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, bs_c, block_n), pdt),
            pltpu.VMEM((bs_r, block_n), jnp.float32),
            pltpu.VMEM((bs_r, block_n), pdt),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA,
        ],
    )
    out, _panel = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((m, n), pdt),
            jax.ShapeDtypeStruct((2, m, block_n), pdt),
        ),
        compiler_params=pltpu.CompilerParams(
            # The HBM panel is shared across ALL grid steps —
            # even the j stripes must run sequentially on one core.
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(
        _slot_table(stacked_w),
        stacked_w.blocks,
        y0.astype(pdt),
        stacked_b[:, :, None],
    )
    return out.astype(out_dtype)
