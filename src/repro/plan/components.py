"""The component layout — a layer that is a union of dense blocks.

A RadiX-net butterfly layer stores 32 edges per row, but its 16×16
block-CSR form scatters them over up to 32 blocks a block-row (one
nonzero per block row at stride 32), so the occupancy-exact kernel runs
one grid step per mostly-zero block. Yet every phase's bipartite
row–column graph splits into disjoint **complete** components: each of
a component's ``r`` rows connects to each of its ``c`` columns. Order
the rows and the columns component by component, and the layer becomes
block-diagonal.

:func:`component_layout` detects that structure from the layer's true
nonzero entries (pad slots and stored zeros are not edges) and engages
only when all three hold:

1. every component is complete;
2. every component has the same shape ``r × c``, with ``r`` at most 128
   and a number of components that is a multiple of 8;
3. the layer has no empty row or column.

It then stores ``group`` components per block-row (a multiple of 8, up
to ``BLOCK_ROWS`` rows) as ``c`` blocks of ``group*r × group``: block
``j`` holds the ``j``-th column of each component, so every row meets
exactly one entry per block. The occupancy-exact kernel then adds each
row's ``c`` terms one per grid step, in ascending column order — the
order of a plain CSR product, so the float32 result is the one
sequential summation gives, while a 16384-neuron RadiX-net layer runs
512 blocks instead of 2,048–32,768. (Dense ``r × c`` blocks would run
fewer bytes, but the matrix unit sums a block's terms in its own order,
which departs from sequential summation by that summation's own
rounding error; see PERF.md.)

The plan (``repro.plan.stack_plan``) runs the re-laid block-CSR weight
through the same ``bcsr_spmm`` kernel, holds each layer's activations in
that layer's row order, and gathers them into the next component
layer's column order in between. Host-side, once per distinct weight
object at plan build.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

# Rows of one block-row at most: the components a grid step covers.
# A step's matrix-unit work grows with the square of the components per
# block-row while the steps fall with it.
BLOCK_ROWS = 1024


@dataclasses.dataclass(frozen=True)
class ComponentLayout:
    """A layer re-laid component by component.

    ``weight[i, j] = W[rows[i], cols[j]]`` for the caller's matrix ``W``.
    Component ``g`` holds rows ``rows[g*r:(g+1)*r]``; a block-row holds
    ``group`` whole components, and its ``j``-th block the ``j``-th
    column of each of them, so every row has one entry in each of the
    block-row's ``c`` blocks.
    """

    weight: BlockCSRMatrix  # (group*r, group) blocks, c per block-row
    rows: np.ndarray  # (m,) row order R, contiguous per component
    cols: np.ndarray  # (k,) column order C, block by block
    shape: tuple[int, int]  # one component's (r, c)

    @property
    def n_components(self) -> int:
        return self.rows.size // self.shape[0]


def _entries(w) -> tuple[np.ndarray, np.ndarray, np.ndarray] | None:
    """(rows, cols, values) of the layer's nonzero entries in stored
    blocks (host copies), or None for a dense weight."""
    if isinstance(w, BlockSparseMatrix):
        blocks = np.asarray(jax.device_get(w.blocks))
        live = np.asarray(jax.device_get(w.block_mask)).astype(bool)
        col_idx = np.asarray(jax.device_get(w.col_idx))
        rb, slot, i, j = np.nonzero((blocks != 0) & live[:, :, None, None])
        rows = rb * w.block_shape[0] + i
        cols = col_idx[rb, slot].astype(np.int64) * w.block_shape[1] + j
        return rows, cols, blocks[rb, slot, i, j]
    if isinstance(w, BlockCSRMatrix):
        values = np.asarray(jax.device_get(w.values))
        live = np.asarray(jax.device_get(w.valid)).astype(bool)
        row_id = np.asarray(jax.device_get(w.row_id)).astype(np.int64)
        col_idx = np.asarray(jax.device_get(w.col_idx)).astype(np.int64)
        t, i, j = np.nonzero((values != 0) & live[:, None, None])
        rows = row_id[t] * w.block_shape[0] + i
        cols = col_idx[t] * w.block_shape[1] + j
        return rows, cols, values[t, i, j]
    return None


def component_layout(w) -> ComponentLayout | None:
    """The layer's component layout, or None where any of the module's
    three conditions fails (or the weight is dense, or the re-laid
    kernel's prefetch tables would not fit SMEM)."""
    from repro.kernels import bcsr_spmm as _bcsr

    found = _entries(w)
    if found is None:
        return None
    rows, cols, vals = found
    m, k = w.shape
    if rows.size == 0:
        return None
    row_deg = np.bincount(rows, minlength=m)
    col_deg = np.bincount(cols, minlength=k)
    c, r = int(row_deg[0]), int(col_deg[0])
    # No empty row or column, and one degree for all of each: what a
    # union of complete r × c components has.
    if c == 0 or r == 0 or (row_deg != c).any() or (col_deg != r).any():
        return None
    group = None if m % r else _group(m // r, r)
    if group is None:
        return None
    order = np.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    sets = cols.reshape(m, c)  # each row's columns, ascending
    if (sets[:, 1:] == sets[:, :-1]).any():
        return None  # an entry stored twice
    # Rows with one column set form a component. With every row of
    # degree c and every column of degree r, a set shared by exactly r
    # rows is a complete component, and no two such sets overlap.
    _, first, inverse, counts = np.unique(
        sets, axis=0, return_index=True, return_inverse=True,
        return_counts=True,
    )
    if (counts != r).any():
        return None
    n_comp = counts.size
    by_first_row = np.argsort(first)
    rank = np.empty_like(by_first_row)
    rank[by_first_row] = np.arange(n_comp)
    comp_of_row = rank[inverse.reshape(-1)]
    row_order = np.argsort(comp_of_row, kind="stable")
    comp_cols = sets[first[by_first_row]]  # (n_comp, c), ascending
    row_pos = np.empty(m, np.int64)
    row_pos[row_order] = np.arange(m)
    col_pos = np.empty(k, np.int64)
    col_pos[comp_cols.reshape(-1)] = np.arange(k)
    dense = np.zeros((n_comp, r, c), vals.dtype)
    dense[comp_of_row[rows], row_pos[rows] % r, col_pos[cols] % c] = vals
    # Block (b, j) takes column j of each of block-row b's components:
    # component i's rows meet only its own column, so each row has one
    # entry per block and the kernel adds a row's terms one at a time,
    # in ascending column order.
    n_rows = n_comp // group
    col_order = (
        comp_cols.reshape(n_rows, group, c).transpose(0, 2, 1).reshape(-1)
    )
    blocks = np.zeros((n_rows, c, group, r, group), vals.dtype)
    own = np.arange(group)
    blocks[:, :, own, :, own] = dense.reshape(n_rows, group, r, c).transpose(
        1, 0, 3, 2
    )
    n_blocks = n_rows * c
    blocks = blocks.reshape(n_blocks, group * r, group)
    # numpy in, so no array here is staged when a plan is built inside
    # a trace
    weight = BlockCSRMatrix(
        jnp.asarray(blocks),
        jnp.asarray(np.arange(0, n_blocks + 1, c, dtype=np.int32)),
        jnp.asarray(np.repeat(np.arange(n_rows, dtype=np.int32), c)),
        jnp.asarray(np.arange(n_blocks, dtype=np.int32)),
        jnp.asarray(np.ones((n_blocks,), bool)),
        (m, k),
        (group * r, group),
    )
    if not _bcsr.smem_fits(weight):
        return None
    return ComponentLayout(weight, row_order, col_order, (r, c))


def _group(n_comp: int, r: int) -> int | None:
    """Components per block-row: the most, a multiple of 8 that divides
    ``n_comp``, whose rows fit ``BLOCK_ROWS``; None if none does."""
    for group in range(BLOCK_ROWS // r // 8 * 8, 0, -8):
        if n_comp % group == 0:
            return group
    return None
