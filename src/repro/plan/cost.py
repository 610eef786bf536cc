"""Exact grid-step accounting — the hardware-independent cost model.

One forward layer on an (·, n) activation panel executes a knowable
number of kernel grid steps; a :class:`~repro.plan.StackPlan` carries
the stack's total as a precomputed property so serving can bill pad
waste without re-deriving the sum per panel. Lifted out of
``repro.core.dnn`` (which keeps ``layer_grid_steps``/``dnn_grid_steps``
as aliases).

Every sparse branch delegates to the kernel module's own ``grid_steps``
formula and reads the block geometry from the weight's layout (NOT the
seed constants), so the model stays exact for autotuner-chosen block
sizes and ``block_n`` — ``tests/test_cost_model.py`` pins it against
the grid the Pallas calls actually launch.
"""

from __future__ import annotations

from typing import Sequence

from repro.kernels import DEFAULT_BLOCK_N
from repro.plan.layout import Weight
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix


def layer_grid_steps(
    w: Weight, n: int, *, block_n: int = DEFAULT_BLOCK_N
) -> int:
    """Exact kernel grid steps one forward layer executes on an (·, n)
    activation panel.

    ELL: ``nrb × max_blocks_per_row × n_tiles`` (the pad is paid on every
    block-row); block-CSR: ``total_nnz_blocks × n_tiles`` (occupancy-
    exact); dense: the full ``(m/bm) × (n/bn) × (k/bk)`` tile grid.
    Mirrors the effective-block-size shrink of ``repro.kernels.ops`` so
    narrow panels are accounted at the tile width they actually run at,
    and reads block geometry from the weight's own layout so tuner-chosen
    block sizes are billed exactly.
    """
    from repro.kernels import bcsr_spmm as _bcsr_kernel
    from repro.kernels import bsr_spmm as _bsr_kernel
    from repro.kernels.ops import _ceil_mult, effective_block_n

    bn = effective_block_n(n, block_n)
    if isinstance(w, BlockCSRMatrix):
        return _bcsr_kernel.grid_steps(w, n, bn)
    if isinstance(w, BlockSparseMatrix):
        return _bsr_kernel.grid_steps(w, n, bn)
    m, k = w.shape
    bm = min(DEFAULT_BLOCK_N, _ceil_mult(m))
    bk = min(DEFAULT_BLOCK_N, _ceil_mult(k))
    return -(-m // bm) * (-(-n // bn)) * -(-k // bk)


def mxv_grid_steps(w: Weight, *, block_n: int = DEFAULT_BLOCK_N) -> int:
    """Exact bill for a GraphBLAS ``mxv``/``vxm`` narrow panel (n = 1).

    The vector rides through the kernels as a ``[:, None]`` panel; the
    effective-tile shrink bottoms out at an 8-wide column tile, so the
    bill is one 8-wide stripe of the weight's grid — NOT a full
    ``DEFAULT_BLOCK_N``-wide tile. Same formula ``plan.mxm`` uses when
    it builds a width-1 plan."""
    return layer_grid_steps(w, 1, block_n=block_n)


def stack_grid_steps(
    weights: Sequence[Weight], n: int, *, block_n: int = DEFAULT_BLOCK_N
) -> int:
    """Total forward grid steps of the L-layer stack on an (m, n) panel:
    the Σ of its layers' grids, one kernel launch each."""
    return sum(layer_grid_steps(w, n, block_n=block_n) for w in weights)


def layer_block_area(w: Weight) -> int:
    """⊗-work units one grid step of this layer performs — the stored
    block's area (``bs_r × bs_c``), or the dense tile's. Grid-step counts
    at DIFFERENT block sizes are not comparable raw (a 32×32 step does 4×
    the MACs of a 16×16 step); the autotuner normalizes by this so
    re-blocked candidates cannot win the cost race by coarsening."""
    from repro.kernels.ops import _ceil_mult

    if isinstance(w, (BlockCSRMatrix, BlockSparseMatrix)):
        bs_r, bs_c = w.block_shape
        return bs_r * bs_c
    m, k = w.shape
    bm = min(DEFAULT_BLOCK_N, _ceil_mult(m))
    bk = min(DEFAULT_BLOCK_N, _ceil_mult(k))
    return bm * bk


def stack_block_work(
    weights: Sequence[Weight], n: int, *, block_n: int = DEFAULT_BLOCK_N
) -> int:
    """Σ layer grid steps × block area — the block-size-invariant cost
    the autotuner ranks candidates by (equal to ``stack_grid_steps × bs²``
    for homogeneous stacks)."""
    return sum(
        layer_grid_steps(w, n, block_n=block_n) * layer_block_area(w)
        for w in weights
    )
