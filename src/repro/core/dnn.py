"""The paper's ReLU DNN (§III, §IV Fig. 4) in JAX.

Execution modes:

* ``dnn_forward(..., fused=False)`` — **paper-faithful**: each layer is
  exactly the three GraphBLAS calls of Fig. 4:

    Y[k+1]  = GrB_mxm(FP32AddMul, W[k], Y[k])          # arithmetic semiring
    Y[k+1]  = GrB_eWiseMult(FP32MaxPlus, Y[k+1], B[k]) # ⊗=+  → bias add
    Y[k+1]  = GrB_eWiseAdd(FP32MaxPlus, Y[k+1], Zero)  # ⊕=max → ReLU

* ``fused=True`` — beyond-paper: one fused sparse-matmul + bias + max
  epilogue per layer (single activation stream; see DESIGN.md §2).

Weight layouts: dense arrays, ELL-padded :class:`BlockSparseMatrix`
(regular topologies) or occupancy-exact :class:`BlockCSRMatrix`
(skewed/pruned topologies — kernel grid ∝ true nnz blocks).
``preferred_layout``/``to_preferred_layout`` encode the choice; every
entry point dispatches on the weight type. ``dnn_forward_scan`` is the
stacked/scanned variant used inside jit for deep networks (one layer
traced once).

Dispatch itself now lives in ``repro.plan`` (layout heuristic, route
decision tree, grid-step cost model, compiled-plan cache — see
``docs/architecture.md``); this module keeps the paper-faithful math
plus backward-compatible wrappers that consult plans instead of
re-deriving dispatch per call.

Training: ``dnn_forward_trainable`` is the ``value_and_grad``-compatible
forward — every sparse layer goes through the custom-VJP Pallas kernel
wrappers (``repro.kernels.ops``), so the backward pass computes
dX = Wᵀ·dY and sparse-preserving weight cotangents with no densify
(``repro.kernels.autodiff``). ``dnn_value_and_grad`` packages the usual
loss → (loss, (dweights, dbiases)) step.
"""

from __future__ import annotations

from typing import Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import graphblas as gb
from repro.core.semiring import MAX_PLUS, PLUS_TIMES
from repro.kernels import DEFAULT_BLOCK_N
from repro.sparse import ops as sparse_ops
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array
Weight = Union[Array, BlockSparseMatrix, BlockCSRMatrix]

# Backward-compatible wrappers — the layout heuristic, grid-step cost
# model, and route decision tree now live in ``repro.plan`` so plans,
# serving, and these legacy entry points all consult ONE implementation.
# ``repro.plan`` imports are deferred to call time: this module is
# imported during ``repro.core``/``repro.sparse`` package init, before
# the plan package can finish loading.


def __getattr__(name: str):
    if name == "ELL_WASTE_THRESHOLD":
        from repro.plan import layout as _plan_layout

        return _plan_layout.ELL_WASTE_THRESHOLD
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def preferred_layout(w: BlockSparseMatrix) -> str:
    """``"ell"`` or ``"bcsr"`` — alias of
    :func:`repro.plan.preferred_layout` (the ELL-pad waste heuristic)."""
    from repro.plan import layout as _plan_layout

    return _plan_layout.preferred_layout(w)


def to_preferred_layout(w: Weight) -> Weight:
    """Alias of :func:`repro.plan.to_preferred_layout`."""
    from repro.plan import layout as _plan_layout

    return _plan_layout.to_preferred_layout(w)


def layer_grid_steps(
    w: Weight, n: int, *, block_n: int = DEFAULT_BLOCK_N
) -> int:
    """Exact kernel grid steps one forward layer executes on an (·, n)
    activation panel (alias of :func:`repro.plan.layer_grid_steps` —
    the hardware-independent cost model, see `docs/serving.md`)."""
    from repro.plan import cost as _plan_cost

    return _plan_cost.layer_grid_steps(w, n, block_n=block_n)


def dnn_grid_steps(
    weights: Sequence[Weight], n: int, *, block_n: int = DEFAULT_BLOCK_N
) -> int:
    """Total forward grid steps of the L-layer stack on an (m, n) panel
    (alias of :func:`repro.plan.stack_grid_steps`; a compiled
    :class:`repro.plan.StackPlan` carries this as its precomputed
    ``grid_steps`` property)."""
    from repro.plan import cost as _plan_cost

    return _plan_cost.stack_grid_steps(weights, n, block_n=block_n)


def dnn_layer(w: Weight, y: Array, b: Array, *, fused: bool = True) -> Array:
    """One forward layer: max(W·Y + b⊗1ᵀ, 0).  y: (m, n); b: (m,)."""
    if fused:
        if isinstance(w, BlockCSRMatrix):
            return sparse_ops.bcsr_matmul_fused_relu(w, y, b)
        if isinstance(w, BlockSparseMatrix):
            return sparse_ops.bsr_matmul_fused_relu(w, y, b)
        return sparse_ops.dense_matmul_fused_relu(w, y, b)
    # Paper-faithful three-call GraphBLAS sequence (Fig. 4 lines 30-32).
    bias = jnp.broadcast_to(b[:, None], y.shape)  # B[k] = b replicated
    zero = jnp.zeros_like(y)  # the Zero matrix (lines 24-26)
    z = gb.mxm(w, y, PLUS_TIMES)  # line 30
    z = gb.ewise_mult(z, bias, MAX_PLUS)  # line 31: ⊗ = +
    z = gb.ewise_add(z, zero, MAX_PLUS)  # line 32: ⊕ = max
    return z


def dnn_forward(
    weights: Sequence[Weight],
    biases: Sequence[Array],
    y0: Array,
    *,
    fused: bool = True,
    mesh=None,
) -> Array:
    """Full L-layer forward pass (the paper's ``dnn()`` function).

    ``mesh``: run the stack mesh-sharded — every sparse layer's
    block-CSR segment is partitioned across the mesh's ``row_blocks``
    axes and executed under ``shard_map`` with a psum between layers
    (``repro.plan.ShardedStackPlan``, fetched through the shared
    :func:`repro.plan.default_cache`). Single-device semantics are
    unchanged when ``mesh`` is None (the default).
    """
    if mesh is not None:
        from repro.plan import default_cache

        plan = default_cache().get(
            weights, biases, max(y0.shape[1], 1), mesh=mesh
        )
        return plan.forward(y0)
    y = y0
    for w, b in zip(weights, biases):
        y = dnn_layer(w, y, b, fused=fused)
    return y


def dnn_forward_all(
    weights: Sequence[Weight],
    biases: Sequence[Array],
    y0: Array,
    *,
    fused: bool = True,
) -> list[Array]:
    """Forward pass returning every Y[k] (the paper's Y[0..L] array)."""
    ys = [y0]
    for w, b in zip(weights, biases):
        ys.append(dnn_layer(w, ys[-1], b, fused=fused))
    return ys


def dnn_layer_trainable(
    w: Weight,
    y: Array,
    b: Array,
    *,
    interpret: bool | None = None,
    transpose_plan=None,
) -> Array:
    """One differentiable layer max(W·Y + b⊗1ᵀ, 0) through the custom-VJP
    kernel wrappers (dense weights use the XLA fused path, which JAX
    differentiates natively). ``transpose_plan`` (for block-CSR weights)
    is the cached backward transpose from a ``repro.plan`` StackPlan —
    without it every backward pass re-sorts the frozen topology."""
    from repro.kernels import ops as kernel_ops

    if isinstance(w, BlockCSRMatrix):
        return kernel_ops.bcsr_spmm(
            w, y, b, transpose_plan, fuse_bias_relu=True, interpret=interpret
        )
    if isinstance(w, BlockSparseMatrix):
        return kernel_ops.bsr_spmm(
            w, y, b, fuse_bias_relu=True, interpret=interpret
        )
    return sparse_ops.dense_matmul_fused_relu(w, y, b)


def _layer_transpose_plans(weights: Sequence[Weight], plan):
    """Per-layer cached transposes from a ``repro.plan`` StackPlan (or
    None → no caching, the legacy re-sort-every-backward behaviour)."""
    if plan is None:
        return (None,) * len(weights)
    if not plan.differentiable:
        raise ValueError(
            "the supplied plan is not differentiable; build it with "
            "differentiable=True (PlanCache.get(..., differentiable=True))"
        )
    if plan.n_layers != len(weights):
        raise ValueError(
            f"plan has {plan.n_layers} layers but the stack has "
            f"{len(weights)}"
        )
    return plan.transpose_plans


def dnn_forward_trainable(
    weights: Sequence[Weight],
    biases: Sequence[Array],
    y0: Array,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    plan=None,
) -> Array:
    """L-layer forward whose backward pass is kernel-resident.

    ``use_kernel=True`` routes every sparse layer through the Pallas
    kernels (custom VJPs: sparse-preserving dW, occupancy-exact dX);
    ``use_kernel=False`` uses the jnp oracle paths (same math, XLA
    autodiff — the pragmatic choice on CPU where kernels interpret).
    Both are ``jax.value_and_grad``-compatible.

    ``plan``: a differentiable :class:`repro.plan.StackPlan` built for
    this topology. Its cached block-CSR transposes make the backward
    sort-free — the frozen topology is sorted once at plan build, not
    once per backward pass. A :class:`repro.plan.ShardedStackPlan`
    routes the whole forward (and its backward) through the mesh-
    sharded shard_map executable instead — fresh values re-shard
    through the plan's frozen partition, cotangents keep the caller's
    unsharded layout.
    """
    if plan is not None and getattr(plan, "is_sharded", False):
        return plan.forward_trainable(
            weights, biases, y0, use_kernel=use_kernel, interpret=interpret
        )
    tps = _layer_transpose_plans(weights, plan)
    y = y0
    for w, b, tp in zip(weights, biases, tps):
        if use_kernel:
            y = dnn_layer_trainable(
                w, y, b, interpret=interpret, transpose_plan=tp
            )
        else:
            y = dnn_layer(w, y, b, fused=True)
    return y


def dnn_value_and_grad(
    weights: Sequence[Weight],
    biases: Sequence[Array],
    y0: Array,
    targets: Array,
    *,
    use_kernel: bool = True,
    interpret: bool | None = None,
    plan=None,
):
    """The paper's DNN as a training step core: mean-squared loss of the
    forward pass against ``targets``, differentiated wrt weights AND
    biases. Returns ``(loss, (dweights, dbiases))`` where sparse weight
    cotangents keep the primal layout (stored blocks only; integer
    topology leaves carry float0 — optimizers skip them by dtype).
    ``plan`` as in :func:`dnn_forward_trainable`."""

    def loss_fn(ws, bs):
        out = dnn_forward_trainable(
            ws, bs, y0, use_kernel=use_kernel, interpret=interpret, plan=plan
        )
        return 0.5 * jnp.mean((out - targets) ** 2)

    return jax.value_and_grad(loss_fn, argnums=(0, 1), allow_int=True)(
        list(weights), list(biases)
    )


def dnn_forward_scan(
    stacked_weights: Weight,
    stacked_biases: Array,
    y0: Array,
    *,
    fused: bool = True,
) -> Array:
    """Scanned forward for homogeneous stacks.

    ``stacked_weights``: dense (L, m, m) array or a BlockSparseMatrix
    pytree whose leaves carry a leading L axis; ``stacked_biases``
    (L, m). One layer body in the HLO regardless of L.
    """

    def body(y, layer):
        w, b = layer
        return dnn_layer(w, y, b, fused=fused), None

    y, _ = jax.lax.scan(body, y0, (stacked_weights, stacked_biases))
    return y

