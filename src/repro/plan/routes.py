"""The route decision tree — how a sparse stack executes.

One decision, made once per (topology, width-class, differentiable?)
key at plan-build time and never re-derived per call:

    homogeneous square BSR stack AND not differentiable AND fused
    allowed AND the stack's slot table fits SMEM?
      └─ residency auto-detected, the plan re-lays its weights with no
               tuned ``layout`` or ``block_size``, and every distinct
               weight is a union of complete components
               (:func:`takes_component_layout`) → **layered**, each
               layer in the component layout (``repro.plan.components``)
      └─ panel fits VMEM → **fused**: ONE VMEM-resident ``pallas_call``
               for the whole stack (``repro.kernels.fused_mlp``)
      └─ panel past ``VMEM_SOFT_LIMIT_BYTES`` → **fused-tiled**: still
               ONE ``pallas_call``, but the ping-pong activation panel
               lives in HBM and the m dimension is tiled over
               the row-block grid
               (``repro.kernels.fused_mlp.fused_mlp_tiled_forward``)
      └─ no  → per-layer dispatch, by execution layout:
               block-CSR → **kernel-bcsr** (occupancy-exact grid; the
                           differentiable backward reuses the plan's
                           cached transpose)
               ELL-BSR   → **kernel-ell** (inference plans re-lay an ELL
                           layer to block-CSR when its pad is wasteful
                           or its prefetch tables overflow SMEM)
               dense     → **kernel-dense** (Pallas tiled matmul), or
                           **xla-dense** when the plan must stay
                           ``jax.grad``-compatible (the dense Pallas
                           kernel has no VJP)
    all layers xla-dense → the stack route reads **xla** (pure-XLA
    fallback); otherwise **layered**.

See ``docs/architecture.md`` for the prose version of this tree.
"""

from __future__ import annotations

from typing import Sequence

from repro.kernels import DEFAULT_BLOCK_N, SMEM_BYTES
from repro.plan.layout import Weight, layer_layout
from repro.sparse.bsr import BlockSparseMatrix

ROUTE_FUSED = "fused"
ROUTE_FUSED_TILED = "fused-tiled"
ROUTE_LAYERED = "layered"
ROUTE_XLA = "xla"
# Mesh-sharded layered route (repro.plan.sharded): per-shard block-CSR
# kernels under shard_map with a psum between layers. Chosen explicitly
# by passing a mesh, never by the single-device decision tree above.
ROUTE_SHARDED = "sharded"


def _fusable_bsr_stack(weights: Sequence[Weight]) -> bool:
    """≥1 layer, all BSR with identical shape / block shape / pad width
    — the structural precondition both fused kernels share — whose
    whole-stack slot table fits SMEM (the TPU compiler refuses the fused
    kernels otherwise: 4096 neurons × 120 layers needs 3.75 MiB).
    (BlockCSRMatrix stacks take the layered path — per-layer
    ``total_blocks`` varies, so there is no static stacked layout.)"""
    from repro.kernels import fused_mlp as _fmlp

    if not weights:
        return False
    first = weights[0]
    if not isinstance(first, BlockSparseMatrix):
        return False
    return all(
        isinstance(w, BlockSparseMatrix)
        and w.shape == first.shape
        and w.block_shape == first.block_shape
        and w.max_blocks_per_row == first.max_blocks_per_row
        for w in weights
    ) and _fmlp.fused_prefetch_fits(first, len(weights))


def resident_eligible(
    weights: Sequence[Weight],
    *,
    block_n: int = DEFAULT_BLOCK_N,
    panel_dtype=None,
    vmem_limit: int | None = None,
) -> bool:
    """Can this stack run through the single-call VMEM-resident kernel?

    Requires: a homogeneous square BSR stack, its slot table within
    SMEM, whose activation panel (at this ``block_n`` and
    ``panel_dtype``) fits the VMEM budget. Stacks past the budget are
    NOT resident-eligible but may still be
    ``fused-tiled``-eligible — :func:`fused_route` makes the three-way
    call. bf16 panels halve the panel bill, so the same stack can be
    resident under ``panel_dtype="bfloat16"`` and tiled under f32.
    """
    from repro.kernels import fused_mlp as _fmlp

    if not _fusable_bsr_stack(weights):
        return False
    return _fmlp.fused_mlp_eligible(
        weights[0], block_n, panel_dtype=panel_dtype, vmem_limit=vmem_limit
    )


def fused_route(
    weights: Sequence[Weight],
    *,
    block_n: int = DEFAULT_BLOCK_N,
    panel_dtype=None,
    vmem_limit: int | None = None,
) -> str | None:
    """Which single-``pallas_call`` fused route (if any) fits this stack.

    ``ROUTE_FUSED`` when the activation panel fits VMEM
    (:func:`resident_eligible`), ``ROUTE_FUSED_TILED`` for a homogeneous
    square BSR stack past the VMEM budget (panel ping-pongs through HBM,
    m tiled over the row-block grid), ``None`` when only the per-layer
    routes apply — including every stack whose slot table overflows
    SMEM. The boundary is exact:
    ``fused_mlp_vmem_bytes(m, block_n, panel_dtype) == vmem_limit``
    (default ``VMEM_SOFT_LIMIT_BYTES``) is the last resident m; one
    block-row more tips into fused-tiled. The autotuner moves this
    boundary through ``panel_dtype`` (bf16 halves the bill) and
    ``vmem_limit`` (silicon-calibrated budget).
    """
    from repro.kernels import fused_mlp as _fmlp

    if not _fusable_bsr_stack(weights):
        return None
    first = weights[0]
    if not _fmlp.fused_mlp_tiled_eligible(first, block_n):  # square check
        return None
    if _fmlp.fused_mlp_eligible(
        first, block_n, panel_dtype=panel_dtype, vmem_limit=vmem_limit
    ):
        return ROUTE_FUSED
    return ROUTE_FUSED_TILED


def takes_component_layout(
    weights: Sequence[Weight], *, relayout: bool = True, tuned=None
) -> bool:
    """Does auto-detected residency send this stack to the layered route
    in the component layout rather than to a fused route?

    True when the plan re-lays its weights (``relayout``), no tuned
    ``layout`` or ``block_size`` overrides the layout, and every
    distinct weight object has a component layout. Such a layer runs a
    few dense-row blocks where the fused kernels run every stored 16×16
    block: at RadiX-net 1024 × 120, 15,360 grid steps a 512-wide panel
    against 983,040. Callers ask only for a stack that has a fused route
    (:func:`fused_route`); ``use_resident=True`` still demands it.
    Detection reads each distinct weight once on the host, and the plan
    build reuses what it found (:func:`~repro.plan.components.component_layout`).
    """
    from repro.plan import components as _components

    if not relayout or any(
        getattr(tuned, knob, None) is not None for knob in ("layout", "block_size")
    ):
        return False
    distinct = {id(w): w for w in weights}.values()
    return all(_components.component_layout(w) is not None for w in distinct)


def layer_path(w: Weight, *, differentiable: bool) -> str:
    """The per-layer execution path for the layered route.

    Raises ValueError for a sparse layer whose kernel the TPU compiler
    would refuse (its prefetch tables overflow SMEM) — there is no
    kernel left to route it to."""
    from repro.kernels import bcsr_spmm as _bcsr
    from repro.kernels import bsr_spmm as _ell

    layout = layer_layout(w)
    if layout == "dense":
        return "xla-dense" if differentiable else "kernel-dense"
    fits = _bcsr.smem_fits(w) if layout == "bcsr" else _ell.smem_fits(w)
    if not fits:
        raise ValueError(
            f"no kernel fits this {layout} layer: its scalar-prefetch "
            f"tables overflow the {SMEM_BYTES // 1024} KiB SMEM "
            f"(shape {w.shape}, block {w.block_shape})"
        )
    return "kernel-bcsr" if layout == "bcsr" else "kernel-ell"
