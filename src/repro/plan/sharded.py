"""Mesh-sharded execution plans — the multi-device sparse stack.

A :class:`~repro.plan.StackPlan` compiles one topology's dispatch for
one device; this module is the same amortization applied across a mesh,
the step the GraphChallenge scaling papers (arXiv:2004.01181,
arXiv:1909.05631) take past single-node memory. A
:class:`ShardedStackPlan`:

* partitions every sparse layer's block-CSR segment across the
  ``row_blocks`` mesh axes with near-equal nnz per shard
  (``repro.sparse.partition`` — built once per topology, like all plan
  analysis);
* compiles ONE shard-local SPMD executable per width class under
  ``jax.shard_map``: each shard runs the occupancy-exact ``bcsr_spmm``
  Pallas kernel over its own sub-segment (partial row products — the
  arithmetic semiring's ⊕ is +, so cuts may straddle rows), a ``psum``
  over the shard axes assembles the full activation panel between
  layers, and the bias + ReLU epilogue runs post-collective;
* bills grid steps **per shard**: each shard's bill is its local
  segment length × column tiles, so the per-shard bills sum to the
  unsharded occupancy-exact bill (plus any Tp-padding remainder when
  ``n_shards`` does not divide nnz — exposed, never hidden);
* stays differentiable: the custom VJPs of ``repro.kernels.autodiff``
  run inside the shard_map body with **per-shard cached transpose
  plans** (each shard's sub-topology is sorted once, at plan build),
  and fresh training values re-shard through a frozen gather
  (``ShardedBlockCSR.rescatter_values``) whose VJP scatters weight
  cotangents back onto the caller's unsharded layout.

Sharded plans live in the same :class:`repro.plan.PlanCache` as
single-device plans; :class:`repro.plan.PlanKey` carries the mesh
fingerprint so the two can never collide. Entry points:
``repro.core.dnn.dnn_forward(..., mesh=...)``,
``serve.SparseDNNEngine(mesh=...)`` (and the ``ContinuousBatcher``
above it), ``train.make_sparse_train_step(plan=sharded_plan)``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.plan import cost as _cost
from repro.plan import layout as _layout
from repro.plan import routes as _routes
from repro.plan.layout import Weight
from repro.plan.stack_plan import PlanKey, topology_fingerprint
from repro.sparse.bcsr import BcsrTransposePlan, BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix
from repro.sparse.partition import (
    ShardedBlockCSR,
    partition_block_csr,
    stack_transpose_plans,
)

Array = jax.Array


def mesh_fingerprint(mesh: Mesh, rules=None) -> str:
    """Stable cache-key component for a mesh's row-block sharding: the
    resolved shard axes, their sizes, AND the device ids. Two meshes
    with the same fingerprint partition a stack identically and run on
    the same devices — a shape-alike mesh over different devices must
    miss, because a plan's shard_map executable is bound to the mesh it
    was built with. ``None`` (no mesh) is the single-device key, so
    sharded and unsharded plans never collide."""
    from repro.distribution.sharding import row_block_axes

    axes = row_block_axes(mesh, rules)
    inner = ",".join(f"{a}={mesh.shape[a]}" for a in axes)
    devs = ",".join(str(d.id) for d in mesh.devices.flat)
    return f"row_blocks[{inner or 'replicated'}]@devices[{devs}]"


@dataclasses.dataclass(frozen=True)
class ShardedLayerPlan:
    """One layer's frozen partition artifacts."""

    index: int
    source_layout: str  # caller's layout ("dense"/"ell"/"bcsr")
    kind: str  # "bcsr" (partitioned) or "dense" (replicated)
    sharded: ShardedBlockCSR | None
    transpose: BcsrTransposePlan | None  # stacked per-shard plans
    grid_steps_per_shard: tuple[int, ...]  # at the plan's width


@dataclasses.dataclass
class ShardedStackPlan:
    """A compiled multi-device execution plan for one sparse stack at
    one width class. Duck-compatible with :class:`repro.plan.StackPlan`
    where serving needs it (``forward``/``grid_steps``/``route``/
    ``pallas_calls``/``compile_count``); extra sharding observability
    rides on top (``grid_steps_per_shard``, ``nnz_per_shard``,
    ``imbalance``)."""

    key: PlanKey
    mesh: Mesh
    axes: tuple[str, ...]  # mesh axes the shard dim spans
    n_shards: int
    layers: tuple[ShardedLayerPlan, ...]
    width: int
    differentiable: bool
    weights: tuple  # per-layer ShardedBlockCSR / replicated dense array
    biases: tuple
    source_weights: tuple  # caller's objects — cache identity check
    source_biases: tuple
    _body: Callable | None = None  # un-jitted shard_map'd forward
    _fn: Callable | None = None  # jitted serving executable
    _compiles: int = 0

    # StackPlan-compatible surface ------------------------------------
    route: str = _routes.ROUTE_SHARDED
    is_sharded: bool = True

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def grid_steps_per_shard(self) -> tuple[int, ...]:
        """Per-shard forward bill (summed over layers) for one panel of
        this plan's width — the accounting `serve` surfaces per step."""
        return tuple(
            sum(lp.grid_steps_per_shard[s] for lp in self.layers)
            for s in range(self.n_shards)
        )

    @property
    def grid_steps(self) -> int:
        """Total kernel grid steps across all shards (Σ of the per-shard
        bills): equals the unsharded occupancy-exact bill whenever
        ``n_shards`` divides each layer's nnz (no Tp-padding remainder);
        ``shard_pad_blocks`` exposes the remainder otherwise."""
        return sum(self.grid_steps_per_shard)

    @property
    def pallas_calls(self) -> int:
        """Kernel launches per shard per forward (one per sparse layer)."""
        return sum(1 for lp in self.layers if lp.kind == "bcsr")

    @property
    def compile_count(self) -> int:
        return self._compiles

    @property
    def transpose_plans(self) -> tuple[BcsrTransposePlan | None, ...]:
        return tuple(lp.transpose for lp in self.layers)

    def nnz_per_shard(self) -> tuple[int, ...]:
        """Stored blocks per shard, summed over the sparse layers."""
        totals = [0] * self.n_shards
        for lp in self.layers:
            if lp.sharded is not None:
                for s, n in enumerate(lp.sharded.nnz_per_shard()):
                    totals[s] += int(n)
        return tuple(totals)

    def imbalance(self) -> float:
        """max-shard-nnz / mean-shard-nnz across the whole stack."""
        nnz = self.nnz_per_shard()
        total = sum(nnz)
        if total == 0:
            return 1.0
        return max(nnz) * self.n_shards / total

    def shard_pad_blocks(self) -> int:
        """Inert padding slots the common per-shard segment length adds
        over true nnz (nonzero only when n_shards ∤ a layer's nnz) —
        each one burns a grid step per column tile, billed honestly in
        ``grid_steps_per_shard``."""
        pad = 0
        for lp in self.layers:
            if lp.sharded is not None:
                nnz = int(lp.sharded.nnz_per_shard().sum())
                pad += lp.sharded.n_shards * lp.sharded.local_total_blocks - nnz
        return pad

    def describe(self) -> dict:
        return {
            "fingerprint": self.key.fingerprint[:12],
            "mesh": self.key.mesh,
            "shards": self.n_shards,
            "width": self.width,
            "differentiable": self.differentiable,
            "route": self.route,
            "layouts": [lp.kind for lp in self.layers],
            "grid_steps": self.grid_steps,
            "grid_steps_per_shard": list(self.grid_steps_per_shard),
            "nnz_per_shard": list(self.nnz_per_shard()),
            "imbalance": self.imbalance(),
            "shard_pad_blocks": self.shard_pad_blocks(),
            "pallas_calls": self.pallas_calls,
            "compiles": self.compile_count,
        }

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def forward(self, y0: Array) -> Array:
        """One forward pass over an (m, k) panel, k ≤ the width class —
        same contract as ``StackPlan.forward``, executed SPMD over the
        mesh: every panel of this width class reuses ONE compiled
        shard_map executable."""
        m, k = y0.shape
        if k > self.width:
            raise ValueError(
                f"panel width {k} exceeds this plan's width class "
                f"{self.width}; fetch a plan for the wider class"
            )
        if k < self.width:
            y0 = jnp.pad(y0, ((0, 0), (0, self.width - k)))
        out = self._fn(
            self.weights, self.transpose_plans, self.biases, y0
        )
        return out[:, :k]

    def lower(self, dtype=jnp.float32):
        """The SPMD executable lowered for one panel of this width class,
        ahead of time (same contract as ``StackPlan.lower``)."""
        y = jax.ShapeDtypeStruct((self.weights[0].shape[1], self.width), dtype)
        return self._fn.lower(
            self.weights, self.transpose_plans, self.biases, y
        )

    def forward_trainable(
        self,
        weights: Sequence[Weight],
        biases: Sequence[Array],
        y0: Array,
        *,
        use_kernel: bool = True,
        interpret: bool | None = None,
    ) -> Array:
        """Differentiable sharded forward with CALLER-supplied (fresh)
        values. The frozen partition re-shards each layer's values with
        one gather (VJP: scatter-add back onto the caller's layout), so
        weight cotangents keep the unsharded primal structure and the
        backward kernels run shard-local on the cached per-shard
        transposes. ``use_kernel=False`` falls back to the replicated
        jnp oracle (same math, XLA autodiff — CPU-bound runs)."""
        del interpret  # the shard_map body decides per-backend, like jit
        if not self.differentiable:
            raise ValueError(
                "forward_trainable needs a differentiable plan; rebuild "
                "with differentiable=True"
            )
        if len(weights) != self.n_layers:
            raise ValueError(
                f"plan has {self.n_layers} layers but the stack has "
                f"{len(weights)}"
            )
        if not use_kernel:
            from repro.core import dnn as _dnn

            y = y0
            for w, b in zip(weights, biases):
                y = _dnn.dnn_layer(w, y, b, fused=True)
            return y
        objs = []
        for lp, w in zip(self.layers, weights):
            if lp.kind == "bcsr":
                if not isinstance(w, BlockCSRMatrix):
                    raise ValueError(
                        "sharded differentiable plans require block-CSR "
                        f"weights; layer {lp.index} is "
                        f"{_layout.layer_layout(w)} (convert with "
                        "BlockCSRMatrix.from_bsr)"
                    )
                objs.append(
                    lp.sharded.with_values(
                        lp.sharded.rescatter_values(w.values)
                    )
                )
            else:
                objs.append(w)
        return self._body(
            tuple(objs), self.transpose_plans, tuple(biases), y0
        )


def _make_sharded_body(plan: ShardedStackPlan) -> Callable:
    """The shard_map'd SPMD forward. Per layer: shard-local
    occupancy-exact SpMM on the sub-segment → psum of the partial row
    products over the shard axes → bias + ReLU post-collective. Weights
    ride as pytree arguments (training substitutes fresh values); the
    in_specs come from the ``repro.distribution.sharding`` rule table."""
    from repro.distribution.sharding import sharded_csr_pspecs
    from repro.kernels import ops as kernel_ops
    from repro.sparse import ops as sparse_ops

    mesh, axes = plan.mesh, plan.axes
    kinds = tuple(lp.kind for lp in plan.layers)

    def local_forward(layer_objs, tps, biases, y):
        for kind, obj, tp, b in zip(kinds, layer_objs, tps, biases):
            if kind == "bcsr":
                local = BlockCSRMatrix(
                    obj.values[0],
                    obj.row_ptr[0],
                    obj.row_id[0],
                    obj.col_idx[0],
                    obj.valid[0],
                    obj.shape,
                    obj.block_shape,
                )
                ltp = None
                if tp is not None:
                    ltp = BcsrTransposePlan(
                        tp.order[0],
                        tp.row_ptr[0],
                        tp.row_id[0],
                        tp.col_idx[0],
                        tp.valid[0],
                        tp.shape,
                        tp.block_shape,
                    )
                # Partial products only: bias/ReLU must wait for the
                # cross-shard sum (non-owned and empty rows read as the
                # semiring zero, so the psum is exact).
                z = kernel_ops.bcsr_spmm(
                    local, y, None, ltp, fuse_bias_relu=False
                )
                if axes:
                    z = jax.lax.psum(z, axes)
                y = jnp.maximum(z + b[:, None], 0.0)
            else:  # dense layer: replicated compute, no collective
                y = sparse_ops.dense_matmul_fused_relu(obj, y, b)
        return y

    w_specs = []
    tp_specs = []
    shard_spec = P(axes) if axes else P()
    for lp, w in zip(plan.layers, plan.weights):
        if lp.kind == "bcsr":
            w_specs.append(sharded_csr_pspecs(w, mesh))
            tp_specs.append(
                None
                if lp.transpose is None
                else jax.tree.map(lambda _: shard_spec, lp.transpose)
            )
        else:
            w_specs.append(P())
            tp_specs.append(None)

    return jax.shard_map(
        local_forward,
        mesh=mesh,
        in_specs=(
            tuple(w_specs),
            tuple(tp_specs),
            jax.tree.map(lambda _: P(), tuple(plan.biases)),
            P(),
        ),
        out_specs=P(),
        check_vma=False,
    )


def build_sharded_plan(
    weights: Sequence[Weight],
    biases: Sequence[Array],
    width: int,
    mesh: Mesh,
    *,
    differentiable: bool = False,
    fingerprint: str | None = None,
    donor: "ShardedStackPlan | None" = None,
) -> ShardedStackPlan:
    """Compile one :class:`ShardedStackPlan` (all per-topology,
    per-mesh analysis: partition, per-shard transposes, bills, SPMD
    executable).

    Layout rules: block-CSR layers are partitioned as-is; ELL layers are
    re-laid to block-CSR at build time for inference plans (the segment
    layout is what partitions) and **rejected** for differentiable plans
    (cotangents must mirror the caller's layout — convert the stack to
    block-CSR first); dense layers run replicated.

    ``donor``: an existing sharded plan for the same (stack, mesh,
    differentiability) at another width class; partition artifacts and
    per-shard transposes are shared by reference, only the bills and the
    executable are per-width (``PlanCache.get`` supplies this).
    """
    from repro.distribution.sharding import (
        mesh_shard_count,
        row_block_axes,
        sharded_csr_pspecs,
        shardings_for,
    )

    weights = tuple(weights)
    biases = tuple(biases)
    if len(weights) != len(biases):
        raise ValueError("weights/biases length mismatch")
    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    if fingerprint is None:
        fingerprint = topology_fingerprint(weights)
    axes = row_block_axes(mesh)
    n_shards = mesh_shard_count(mesh)
    mesh_fp = mesh_fingerprint(mesh)
    key = PlanKey(fingerprint, width, differentiable, mesh=mesh_fp)

    if donor is not None and (
        donor.key.fingerprint != fingerprint
        or donor.differentiable != differentiable
        or donor.key.mesh != mesh_fp
        or donor.n_layers != len(weights)
    ):
        raise ValueError(
            "donor plan does not match this stack's plan key "
            "(fingerprint / differentiable / mesh / layers)"
        )

    layer_plans = []
    exec_weights = []
    # one partition per weight object, however many layers repeat it
    parts: dict[int, tuple] = {}
    for i, w in enumerate(weights):
        src_layout = _layout.layer_layout(w)
        if isinstance(w, BlockSparseMatrix) and differentiable:
            raise ValueError(
                "sharded differentiable plans require block-CSR "
                f"weights; layer {i} is ELL (convert with "
                "BlockCSRMatrix.from_bsr so weight cotangents keep "
                "the caller's layout)"
            )
        if isinstance(w, (BlockSparseMatrix, BlockCSRMatrix)):
            if donor is not None:
                # width-independent artifacts (partition, transposes —
                # including any ELL→CSR relayout baked into them) are
                # shared by reference; only bills are per-width
                dlp = donor.layers[i]
                sharded, tp = dlp.sharded, dlp.transpose
            elif id(w) in parts:
                sharded, tp = parts[id(w)]
            else:
                ew = (
                    BlockCSRMatrix.from_bsr(w)
                    if isinstance(w, BlockSparseMatrix)
                    else w
                )
                sharded = partition_block_csr(ew, n_shards)
                # placed on the mesh once, not re-sent with every panel
                sharded = jax.device_put(
                    sharded,
                    shardings_for(None, mesh, sharded_csr_pspecs(sharded, mesh)),
                )
                tp = (
                    stack_transpose_plans(sharded)
                    if differentiable
                    else None
                )
                parts[id(w)] = (sharded, tp)
            bills = tuple(
                _cost.layer_grid_steps(sharded.shard(s), width)
                for s in range(n_shards)
            )
            layer_plans.append(
                ShardedLayerPlan(i, src_layout, "bcsr", sharded, tp, bills)
            )
            exec_weights.append(sharded)
        else:  # dense: replicated — every shard pays the full tile grid
            bill = _cost.layer_grid_steps(w, width)
            layer_plans.append(
                ShardedLayerPlan(
                    i, src_layout, "dense", None, None, (bill,) * n_shards
                )
            )
            exec_weights.append(w)

    plan = ShardedStackPlan(
        key=key,
        mesh=mesh,
        axes=axes,
        n_shards=n_shards,
        layers=tuple(layer_plans),
        width=width,
        differentiable=differentiable,
        weights=tuple(exec_weights),
        biases=biases,
        source_weights=weights,
        source_biases=biases,
    )
    body = _make_sharded_body(plan)
    plan._body = body

    def run(layer_objs, tps, bs, y):
        plan._compiles += 1
        return body(layer_objs, tps, bs, y)

    plan._fn = jax.jit(run)
    return plan
