"""Per-layer layout choice — the ELL-pad waste heuristic.

The ELL grid runs ``nrb × max_blocks_per_row`` steps per column tile
(the pad is paid on every block-row); the occupancy-exact CSR grid runs
``total_nnz_blocks``. This module owns the choice rule — lifted out of
``repro.core.dnn`` so every execution path (plans, serving, training,
the legacy wrappers) consults ONE heuristic instead of re-deriving it
per call. ``repro.core.dnn.preferred_layout`` remains as a
backward-compatible alias.
"""

from __future__ import annotations

from typing import Union

import jax

from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

Weight = Union[jax.Array, BlockSparseMatrix, BlockCSRMatrix]

# A block-row whose ELL pad wastes more than this fraction of its slots
# (1 - nnz / (nrb·mbpr)) is better served by the occupancy-exact grid.
ELL_WASTE_THRESHOLD = 0.25


def layer_layout(w: Weight) -> str:
    """The storage layout of a weight: ``"dense"``, ``"ell"``, ``"bcsr"``."""
    if isinstance(w, BlockCSRMatrix):
        return "bcsr"
    if isinstance(w, BlockSparseMatrix):
        return "ell"
    return "dense"


def preferred_layout(w: BlockSparseMatrix) -> str:
    """``"ell"`` or ``"bcsr"`` — which kernel grid wastes less work.

    Choose CSR once the pad's wasted fraction crosses
    :data:`ELL_WASTE_THRESHOLD` (host-side: reads the mask), or when the
    ELL kernel's prefetch tables would not fit SMEM (the TPU compiler
    refuses that kernel).
    """
    from repro.kernels import bsr_spmm as _ell

    if not _ell.smem_fits(w):
        return "bcsr"
    nrb, mbpr = w.col_idx.shape
    # numpy, not w.nnz_blocks: a jnp reduction would turn into a tracer
    # inside a trace context even on a concrete (closed-over) mask,
    # and plan builds may happen while tracing (graphblas.mxm routing).
    import numpy as np

    nnz = int(np.asarray(jax.device_get(w.block_mask)).sum())
    waste = 1.0 - nnz / float(nrb * mbpr)
    return "bcsr" if waste > ELL_WASTE_THRESHOLD else "ell"


def to_preferred_layout(w: Weight) -> Weight:
    """Re-layout an ELL weight to block-CSR when its occupancy is skewed
    enough for the occupancy-exact grid to win (host-side; identity for
    dense and already-CSR weights)."""
    if isinstance(w, BlockSparseMatrix) and preferred_layout(w) == "bcsr":
        return BlockCSRMatrix.from_bsr(w)
    return w
