"""The route decision tree — how a sparse stack executes.

One decision, made once per (topology, width-class, differentiable?)
key at plan-build time and never re-derived per call. A single-device
stack runs one kernel launch per layer; the plan picks each layer's
execution layout, then its kernel:

    inference plan and the layer is a union of complete components?
      └─ yes → **component**: re-laid as block-CSR blocks of whole
               components (``repro.plan.components``), run by
               **kernel-bcsr**
      └─ no  → per-layer dispatch, by execution layout
               (:func:`layer_path`):
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

A tuned ``layout`` or ``block_size`` (``repro.tune``) keeps the
component layout off. A mesh takes the **sharded** route instead
(``repro.plan.sharded``).

See ``docs/architecture.md`` for the prose version of this tree.
"""

from __future__ import annotations

from repro.kernels import SMEM_BYTES
from repro.plan.layout import Weight, layer_layout

ROUTE_LAYERED = "layered"
ROUTE_XLA = "xla"
# Mesh-sharded layered route (repro.plan.sharded): per-shard block-CSR
# kernels under shard_map with a psum between layers. Chosen explicitly
# by passing a mesh, never by the single-device decision tree above.
ROUTE_SHARDED = "sharded"


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
