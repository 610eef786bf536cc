"""The grid-step cost model (``repro.plan.cost``) pinned against the
grids the Pallas calls actually launch.

``layer_grid_steps`` claims to bill EXACTLY the kernel grid — these
tests intercept ``pl.pallas_call`` to capture every launched grid and
compare step products, across layouts (ELL / block-CSR / dense), the
layered stack route, non-default ``block_n``, tuner-chosen block sizes,
and the narrow-panel effective-block shrink."""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro import plan as P
from repro.kernels import DEFAULT_BLOCK_N, ops
from repro.sparse import BlockCSRMatrix, BlockSparseMatrix


@pytest.fixture
def captured_grids(monkeypatch):
    """Record the grid of every pallas_call launched inside the test.

    The public wrappers are jit'd, so a shape seen earlier in the
    process would replay from the jit cache without re-tracing (and
    without re-entering pallas_call) — clear the caches first so every
    dispatch under test traces and is captured.
    """
    grids: list[tuple[int, ...]] = []
    real = pl.pallas_call

    def spy(*args, **kwargs):
        grid = kwargs.get("grid")
        if grid is None and "grid_spec" in kwargs:
            grid = kwargs["grid_spec"].grid
        if grid is not None:
            grids.append(tuple(int(g) for g in grid))
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", spy)
    jax.clear_caches()
    return grids


def _steps(grids) -> int:
    return sum(math.prod(g) for g in grids)


class TestLayerGridSteps:
    def test_ell(self, captured_grids):
        w = BlockSparseMatrix.random(
            jax.random.PRNGKey(0), (96, 64), (16, 16), blocks_per_row=3
        )
        x = jnp.ones((64, 256), jnp.float32)
        ops.bsr_spmm(w, x).block_until_ready()
        assert len(captured_grids) == 1
        assert _steps(captured_grids) == P.layer_grid_steps(w, 256)

    def test_bcsr(self, captured_grids):
        w = BlockCSRMatrix.random_skewed(3, (128, 128), (16, 16), 30, skew=0.5)
        x = jnp.ones((128, 256), jnp.float32)
        ops.bcsr_spmm(w, x).block_until_ready()
        assert len(captured_grids) == 1
        assert _steps(captured_grids) == P.layer_grid_steps(w, 256)

    def test_dense(self, captured_grids):
        w = jnp.ones((256, 256), jnp.float32)
        x = jnp.ones((256, 256), jnp.float32)
        ops.semiring_matmul(w, x).block_until_ready()
        assert len(captured_grids) == 1
        assert _steps(captured_grids) == P.layer_grid_steps(w, 256)

    def test_nondefault_block_n(self, captured_grids):
        w = BlockSparseMatrix.random(
            jax.random.PRNGKey(1), (64, 64), (16, 16), blocks_per_row=2
        )
        x = jnp.ones((64, 256), jnp.float32)
        ops.bsr_spmm(w, x, block_n=64).block_until_ready()
        assert _steps(captured_grids) == P.layer_grid_steps(
            w, 256, block_n=64
        )
        assert P.layer_grid_steps(w, 256, block_n=64) == 2 * P.layer_grid_steps(
            w, 256, block_n=DEFAULT_BLOCK_N
        )

    def test_narrow_panel_effective_shrink(self, captured_grids):
        # A 16-wide panel runs at the shrunk effective tile, not 128 —
        # the model must bill the same shrink the wrapper applies.
        w = BlockSparseMatrix.random(
            jax.random.PRNGKey(2), (64, 64), (16, 16), blocks_per_row=2
        )
        x = jnp.ones((64, 16), jnp.float32)
        ops.bsr_spmm(w, x).block_until_ready()
        assert _steps(captured_grids) == P.layer_grid_steps(w, 16)

    def test_tuner_chosen_block_size(self, captured_grids):
        # The model reads block geometry from the weight's OWN layout —
        # a 32×32 re-blocked matrix bills its own (coarser) grid.
        w16 = BlockCSRMatrix.random_skewed(
            5, (128, 128), (16, 16), 24, skew=0.2
        )
        w32 = BlockCSRMatrix.from_dense(w16.to_dense(), (32, 32))
        x = jnp.ones((128, 128), jnp.float32)
        ops.bcsr_spmm(w32, x).block_until_ready()
        assert _steps(captured_grids) == P.layer_grid_steps(w32, 128)
        assert P.layer_grid_steps(w32, 128) != P.layer_grid_steps(w16, 128)


class TestStackGridSteps:
    def test_layered_stack_sums_layers(self, captured_grids):
        ws = [
            BlockSparseMatrix.random(
                jax.random.PRNGKey(7), (64, 128), (16, 16), blocks_per_row=3
            ),
            BlockCSRMatrix.random_skewed(8, (64, 64), (16, 16), 9, skew=0.6),
        ]
        bs = [jnp.zeros((64,), jnp.float32)] * 2
        plan = P.build_plan(ws, bs, 128, relayout=False)
        assert plan.route == P.ROUTE_LAYERED
        plan.forward(jnp.ones((128, 128), jnp.float32)).block_until_ready()
        assert _steps(captured_grids) == P.stack_grid_steps(ws, 128)


class TestBlockWork:
    def test_block_work_is_block_size_invariant_for_dense_pattern(self):
        # A fully-dense pattern stored at 16×16 vs 32×32 covers the same
        # nonzeros — grid steps differ 4×, block work is identical.
        dense = jnp.asarray(
            jax.random.normal(jax.random.PRNGKey(9), (128, 128))
        )
        w16 = BlockCSRMatrix.from_dense(dense, (16, 16))
        w32 = BlockCSRMatrix.from_dense(dense, (32, 32))
        assert P.layer_grid_steps(w16, 128) == 4 * P.layer_grid_steps(w32, 128)
        assert P.stack_block_work([w16], 128) == P.stack_block_work(
            [w32], 128
        )
