"""Compile-once execution plans for sparse DNN stacks.

Every entry point used to re-derive *how* to run a stack on every call:
layout choice, fused-residency eligibility, grid-step billing, and —
worst — the block-CSR backward re-sorted the frozen topology every
single backward pass. A :class:`StackPlan` does all of that analysis
ONCE per ``(topology-fingerprint, panel-width class, differentiable?)``
key (the GraphChallenge amortization pattern: the topology is fixed,
the per-topology analysis should be too) and carries:

* the chosen layout per layer (the ELL-pad waste heuristic of
  ``repro.plan.layout``, applied at build time instead of per call, or
  the component layout of ``repro.plan.components`` with the row
  gathers that carry activations between its row and column orders);
* the route — fused / layered / XLA fallback (``repro.plan.routes``);
* the exact grid-step bill for the plan's panel width
  (``repro.plan.cost``);
* the **cached block-CSR transpose** (sorted layout + permutation,
  ``BcsrTransposePlan``) so differentiable paths never re-sort;
* a **jitted executable** per plan — serving quantizes panel widths to
  a small set of classes (:func:`quantize_width`) and reuses compiled
  plans instead of recompiling on every new panel width.

Plans are built through :class:`repro.plan.PlanCache`; the legacy entry
points (``repro.core.dnn``, ``repro.serve``) stay as thin wrappers.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import DEFAULT_BLOCK_N
from repro.plan import components as _components
from repro.plan import cost as _cost
from repro.plan import layout as _layout
from repro.plan import routes as _routes
from repro.plan.layout import Weight
from repro.sparse.bcsr import BcsrTransposePlan, BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array

# Panel-width classes serving quantizes to by default: one compiled
# executable per class instead of one per distinct request-batch width.
DEFAULT_WIDTH_CLASSES = (8, 16, 32, 64, 128, 256, 512)


def quantize_width(n: int, classes: Sequence[int] | None = None) -> int:
    """Smallest width class covering an ``n``-column panel.

    ``classes=None`` → identity (no quantization). Widths beyond the
    largest class round up to a multiple of it.
    """
    if not classes:
        return n
    for c in sorted(classes):
        if n <= c:
            return c
    top = max(classes)
    return -(-n // top) * top


def topology_fingerprint(weights: Sequence[Weight]) -> str:
    """Hash of the stack's *topology*: per-layer layout class, shapes,
    and index/mask arrays — NOT the stored values. Two stacks share a
    fingerprint iff every plan-relevant decision (layouts, routes, grid
    bills, transposes) is identical for both. Host-side (one device_get
    per topology; callers cache the result)."""
    h = hashlib.sha1()
    for w in weights:
        if isinstance(w, BlockCSRMatrix):
            h.update(b"bcsr")
            h.update(repr((w.shape, w.block_shape, w.total_blocks)).encode())
            for arr in (w.row_ptr, w.row_id, w.col_idx, w.valid):
                h.update(np.asarray(jax.device_get(arr)).tobytes())
        elif isinstance(w, BlockSparseMatrix):
            h.update(b"ell")
            h.update(
                repr((w.shape, w.block_shape, w.max_blocks_per_row)).encode()
            )
            for arr in (w.col_idx, w.block_mask):
                h.update(np.asarray(jax.device_get(arr)).tobytes())
        else:
            h.update(b"dense")
            h.update(repr(tuple(w.shape)).encode())
    return h.hexdigest()


class PlanKey(NamedTuple):
    """What a compiled plan is keyed on. Same topology + same width
    class + same differentiability (+ same residency request, + same
    mesh) → the same plan, hence a cache hit and zero recompiles.

    ``mesh`` is the mesh/shard fingerprint
    (:func:`repro.plan.sharded.mesh_fingerprint`) for sharded plans and
    ``None`` for single-device plans — a sharded and an unsharded plan
    for the same topology can NEVER collide in a cache.

    ``tuned`` is the :meth:`repro.tune.TunedConfig.token` of the tuning
    entry the plan was built under, or ``None`` for plans built on the
    hand-picked defaults — so a tuned and an untuned plan for the same
    topology can never collide either.

    ``semiring`` is the ⊕.⊗ algebra the plan's executable computes
    (``repro.core.semiring`` registry name). DNN stack plans are always
    ``plus_times``; the GraphBLAS ``mxm``/``mxv`` plans
    (:mod:`repro.plan.mxm`) key their algebra here so a ``plus_times``
    and a ``min_plus`` plan over the same topology can never collide."""

    fingerprint: str
    width: int
    differentiable: bool
    resident: bool | None  # the use_resident tri-state the caller asked
    mesh: str | None = None  # mesh/shard fingerprint, None = unsharded
    tuned: str | None = None  # TunedConfig token, None = default constants
    semiring: str = "plus_times"  # the plan's ⊕.⊗ algebra


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """One layer's precomputed execution decisions."""

    index: int
    source_layout: str  # layout of the caller's weight ("dense"/"ell"/"bcsr")
    layout: str  # execution layout: "dense"/"ell"/"bcsr"/"component"
    path: str  # routes.layer_path value, or "fused"/"fused-tiled"
    grid_steps: int  # exact bill at the plan's width
    transpose_plan: BcsrTransposePlan | None  # cached backward transpose
    # Row gather into this layer's column order, applied to the held
    # activations before the layer (None: they are in order already).
    gather: Array | None = None


@dataclasses.dataclass
class StackPlan:
    """A compiled execution plan for one sparse stack at one width class.

    Built by :func:`build_plan` (usually via ``PlanCache.get``). The
    plan binds the weights/biases it was built from — serving weights
    are frozen, so ``forward(y0)`` reuses the same jitted executable for
    every panel of this width class. Training passes fresh values
    through :meth:`forward_trainable`, which only consumes the plan's
    topology artifacts (layouts + cached transposes).
    """

    key: PlanKey
    route: str  # routes.ROUTE_FUSED / ROUTE_FUSED_TILED / ROUTE_LAYERED / ROUTE_XLA
    layers: tuple[LayerPlan, ...]
    width: int
    differentiable: bool
    grid_steps: int  # exact forward bill for one width-wide panel
    weights: tuple  # execution weights (post-relayout, bound values)
    biases: tuple
    source_weights: tuple  # caller's objects — cache identity check
    source_biases: tuple
    tuned: object | None = None  # the TunedConfig the plan was built under
    # Row gather back to natural order after the last layer (None: the
    # last layer's rows are in natural order).
    out_gather: Array | None = None
    _stacked: tuple | None = None  # (stacked_w, stacked_b) for fused
    # The layered executable's (weights, biases, gathers): each object once
    _args: tuple | None = None
    _fn: Callable | None = None
    _compiles: int = 0

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def is_fused_route(self) -> bool:
        """Single-``pallas_call`` whole-stack route (resident or tiled)."""
        return self.route in (
            _routes.ROUTE_FUSED,
            _routes.ROUTE_FUSED_TILED,
        )

    @property
    def pallas_calls(self) -> int:
        """Kernel launches one forward of this plan performs."""
        if self.is_fused_route:
            return 1
        return sum(1 for lp in self.layers if lp.path != "xla-dense")

    @property
    def compile_count(self) -> int:
        """Times the executable was traced (→ compiled) so far."""
        return self._compiles

    @property
    def layouts(self) -> tuple[str, ...]:
        return tuple(lp.layout for lp in self.layers)

    @property
    def transpose_plans(self) -> tuple[BcsrTransposePlan | None, ...]:
        return tuple(lp.transpose_plan for lp in self.layers)

    @property
    def component_layers(self) -> int:
        """Layers that run in the component layout."""
        return sum(1 for lp in self.layers if lp.layout == "component")

    @property
    def weight_args(self) -> int:
        """Weight arrays the executable takes: one stacked weight on a
        fused route, else each distinct layer weight once."""
        return 1 if self.is_fused_route else len(self._args[0])

    @property
    def gathers(self) -> tuple[Array | None, ...]:
        """The executable's row gathers: one per layer, then the last."""
        return tuple(lp.gather for lp in self.layers) + (self.out_gather,)

    def describe(self) -> dict:
        """JSON-ready summary (docs/architecture.md shows one)."""
        return {
            "fingerprint": self.key.fingerprint[:12],
            "width": self.width,
            "differentiable": self.differentiable,
            "route": self.route,
            "layouts": list(self.layouts),
            "paths": [lp.path for lp in self.layers],
            "component_layers": self.component_layers,
            "grid_steps": self.grid_steps,
            "pallas_calls": self.pallas_calls,
            "cached_transposes": sum(
                1 for lp in self.layers if lp.transpose_plan is not None
            ),
            "compiles": self.compile_count,
            "tuned": self.key.tuned,
        }

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    def forward(self, y0: Array) -> Array:
        """One forward pass of the bound stack over an (m, k) panel,
        k ≤ the plan's width. The panel is padded to the width class so
        every call of this plan reuses ONE compiled executable."""
        m, k = y0.shape
        if k > self.width:
            raise ValueError(
                f"panel width {k} exceeds this plan's width class "
                f"{self.width}; fetch a plan for the wider class"
            )
        if k < self.width:
            y0 = jnp.pad(y0, ((0, 0), (0, self.width - k)))
        return self._fn(*self._bound(), y0)[:, :k]

    def _bound(self) -> tuple:
        """The executable's arguments besides the panel."""
        if self.is_fused_route:
            return self._stacked
        return self._args

    def lower(self, dtype=jnp.float32):
        """The executable lowered for one panel of this width class, ahead
        of time: ``.compile().as_text()`` shows what the device runs
        (``tpu_custom_call`` for each Pallas kernel)."""
        y = jax.ShapeDtypeStruct((self.weights[0].shape[1], self.width), dtype)
        return self._fn.lower(*self._bound(), y)

    def forward_trainable(
        self,
        weights: Sequence[Weight],
        biases: Sequence[Array],
        y0: Array,
        *,
        use_kernel: bool = True,
        interpret: bool | None = None,
    ) -> Array:
        """Differentiable forward with CALLER-supplied (fresh) values —
        the plan contributes only its frozen-topology artifacts, most
        importantly the cached block-CSR transposes, so a train step
        built on this never re-sorts the topology."""
        if not self.differentiable:
            raise ValueError(
                "forward_trainable needs a differentiable plan; rebuild "
                "with differentiable=True"
            )
        from repro.core import dnn as _dnn

        y = y0
        for lp, w, b in zip(self.layers, weights, biases):
            if use_kernel:
                y = _dnn.dnn_layer_trainable(
                    w, y, b, interpret=interpret,
                    transpose_plan=lp.transpose_plan,
                )
            else:
                y = _dnn.dnn_layer(w, y, b, fused=True)
        return y


def _make_executable(plan: StackPlan) -> Callable:
    """The plan's jitted forward. Weights ride as pytree arguments (not
    closure constants) so value updates never retrace; the trace counter
    increments exactly once per compilation, which is how serving counts
    recompiles per width class."""
    from repro.kernels import ops as kernel_ops
    from repro.sparse import ops as sparse_ops

    # Tuned plans thread their overrides into every kernel call; untuned
    # plans pass nothing so the wrappers run on the hand-picked defaults.
    block_n = _tuned_attr(plan.tuned, "block_n") or DEFAULT_BLOCK_N
    panel_dtype = _tuned_attr(plan.tuned, "panel_dtype")
    fused_kw = {"block_n": block_n, "panel_dtype": panel_dtype}

    if plan.route == _routes.ROUTE_FUSED:

        def run_fused(stacked_w, stacked_b, y):
            plan._compiles += 1
            return kernel_ops.fused_mlp_forward(stacked_w, stacked_b, y, **fused_kw)

        return jax.jit(run_fused)

    if plan.route == _routes.ROUTE_FUSED_TILED:

        def run_fused_tiled(stacked_w, stacked_b, y):
            plan._compiles += 1
            return kernel_ops.fused_mlp_tiled_forward(
                stacked_w, stacked_b, y, **fused_kw
            )

        return jax.jit(run_fused_tiled)

    paths = tuple(lp.path for lp in plan.layers)
    tps = plan.transpose_plans
    # A stack that repeats a few phase weights through its layers passes
    # each object once, and each layer reads its own by index: the
    # compiler counts every argument in device memory, and 120 copies of
    # 65536-neuron phase weights (30 GB) would not fit one chip.
    (ws, w_at), (bs, b_at), (gs, g_at) = (
        _distinct(x) for x in (plan.weights, plan.biases, plan.gathers)
    )
    plan._args = (ws, bs, gs)

    def rows(y, g):
        if g is None:
            return y
        return y.at[g].get(mode="promise_in_bounds", unique_indices=True)

    def run_layered(weights, biases, gathers, y):
        plan._compiles += 1
        for i, (path, tp) in enumerate(zip(paths, tps)):
            y = rows(y, gathers[g_at[i]])
            w, b = weights[w_at[i]], biases[b_at[i]]
            if path == "kernel-bcsr":
                y = kernel_ops.bcsr_spmm(
                    w, y, b, tp, fuse_bias_relu=True, block_n=block_n
                )
            elif path == "kernel-ell":
                y = kernel_ops.bsr_spmm(
                    w, y, b, fuse_bias_relu=True, block_n=block_n
                )
            elif path == "kernel-dense":
                y = kernel_ops.semiring_matmul(
                    w, y, b, fuse_bias_relu=True, block_n=block_n
                )
            else:  # xla-dense: grad-compatible fused XLA form
                y = sparse_ops.dense_matmul_fused_relu(w, y, b)
        return rows(y, gathers[g_at[-1]])

    return jax.jit(run_layered)


def _distinct(items: Sequence) -> tuple[tuple, tuple[int, ...]]:
    """The distinct objects of ``items`` by identity, in first-seen
    order, and the index of each item among them."""
    seen: dict[int, tuple[int, object]] = {}
    at = tuple(seen.setdefault(id(x), (len(seen), x))[0] for x in items)
    return tuple(x for _, x in seen.values()), at


def _tuned_attr(tuned, name: str):
    """Read one knob off a TunedConfig-shaped object (duck-typed so the
    plan layer never imports ``repro.tune``); None when untuned."""
    return None if tuned is None else getattr(tuned, name, None)


def _reblock(w: Weight, block_size: int) -> Weight:
    """Re-block a sparse execution weight through its dense form (host-
    side, plan-build-time only). Keeps the execution layout family."""
    if isinstance(w, BlockCSRMatrix):
        return BlockCSRMatrix.from_dense(
            np.asarray(jax.device_get(w.to_dense())), (block_size, block_size)
        )
    if isinstance(w, BlockSparseMatrix):
        return BlockSparseMatrix.from_dense(
            np.asarray(jax.device_get(w.to_dense())), (block_size, block_size)
        )
    return w


def _force_layout(w: Weight, layout: str) -> Weight:
    """Tuner override of the ELL-waste heuristic: force the execution
    layout of a sparse weight (identity for dense weights)."""
    if layout == "bcsr" and isinstance(w, BlockSparseMatrix):
        return BlockCSRMatrix.from_bsr(w)
    if layout == "ell" and isinstance(w, BlockCSRMatrix):
        return w.to_bsr()
    return w


def _row_gathers(comps: Sequence) -> tuple[list, Array | None]:
    """The row gathers of a layered stack, from each layer's
    :class:`~repro.plan.components.ComponentLayout` (None for a layer in
    natural order): one before each layer, then one back to natural
    order. A component layer leaves its activations in its row order
    ``R``; the next layer reads them in its column order ``C``, so its
    gather is ``R⁻¹[C]``. Identity gathers are None, and equal ones are
    one device array."""
    made: dict[bytes, Array] = {}

    def gather(held, want):
        if held is None and want is None:
            return None
        idx = np.arange(want.size) if held is None else np.argsort(held)
        if want is not None:
            idx = idx[want]
        if np.array_equal(idx, np.arange(idx.size)):
            return None
        key = idx.astype(np.int32).tobytes()
        if key not in made:
            made[key] = jnp.asarray(idx, jnp.int32)
        return made[key]

    held = None  # the row order the activations are held in
    gathers = []
    for comp in comps:
        gathers.append(gather(held, None if comp is None else comp.cols))
        held = None if comp is None else comp.rows
    return gathers, gather(held, None)


def build_plan(
    weights: Sequence[Weight],
    biases: Sequence[Array],
    width: int,
    *,
    differentiable: bool = False,
    use_resident: bool | None = None,
    relayout: bool | None = None,
    fingerprint: str | None = None,
    donor: "StackPlan | None" = None,
    tuned=None,
) -> StackPlan:
    """Compile one :class:`StackPlan` (all the per-topology analysis).

    ``use_resident``: None auto-detects fused eligibility, True demands
    it (ValueError when ineligible), False forces the layered route —
    the ``SparseDNNEngine`` tri-state, verbatim. ``relayout`` applies
    the component layout (``repro.plan.components``) or else the
    ELL→CSR waste heuristic to the bound execution weights; default on
    for inference plans, always off for differentiable plans (their
    cotangents must mirror the caller's layout).

    ``donor``: an existing plan for the SAME stack (same fingerprint,
    differentiability, and residency request) at a different width
    class. Only the width-dependent pieces (grid-step bill, executable)
    are rebuilt; the width-independent topology artifacts — relayouted
    execution weights and biases, row gathers, cached transposes (so
    the topology is still sorted exactly once no matter how many width
    classes serve it), and the fused weight stack — are shared by
    reference.
    ``PlanCache.get`` supplies this automatically.

    ``tuned``: a :class:`repro.tune.TunedConfig` (duck-typed — the plan
    layer only reads its fields) consulted BEFORE the hand-picked
    defaults: ``block_n`` feeds every kernel call and the grid bill,
    ``panel_dtype``/``vmem_limit_bytes`` move the resident↔tiled
    boundary, ``layout`` overrides the ELL-waste heuristic, and
    ``block_size`` re-blocks layered execution weights; either of the
    last two keeps the component layout off. The config's
    token lands in :attr:`PlanKey.tuned` so tuned and untuned plans
    never collide in a :class:`~repro.plan.PlanCache`.
    """
    weights = tuple(weights)
    biases = tuple(biases)
    if len(weights) != len(biases):
        raise ValueError("weights/biases length mismatch")
    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    if fingerprint is None:
        fingerprint = topology_fingerprint(weights)

    tuned_token = None if tuned is None else tuned.token()
    t_block_n = _tuned_attr(tuned, "block_n") or DEFAULT_BLOCK_N
    t_panel = _tuned_attr(tuned, "panel_dtype")
    t_vmem = _tuned_attr(tuned, "vmem_limit_bytes")
    t_layout = _tuned_attr(tuned, "layout")
    t_block_size = _tuned_attr(tuned, "block_size")

    # fused_ok: which single-pallas_call route structurally fits —
    # ROUTE_FUSED (panel resident in VMEM), ROUTE_FUSED_TILED (panel
    # past the VMEM budget, ping-ponged through HBM), or None.
    fused_ok = (
        None
        if differentiable
        else _routes.fused_route(
            weights,
            block_n=t_block_n,
            panel_dtype=t_panel,
            vmem_limit=t_vmem,
        )
    )
    if use_resident and fused_ok is None:
        raise ValueError(
            "use_resident=True but the stack is not eligible for the "
            "fused whole-stack kernels"
            + (
                " (differentiable plans route around their missing VJP)"
                if differentiable
                else " (needs a homogeneous square BSR stack)"
            )
        )
    if use_resident is None or use_resident:
        route = fused_ok or _routes.ROUTE_LAYERED
    else:
        route = _routes.ROUTE_LAYERED

    if relayout is None:
        relayout = not differentiable
    if differentiable and relayout:
        raise ValueError(
            "relayout converts bound weights; a differentiable plan "
            "must keep the caller's layouts so cotangents line up"
        )

    if donor is not None:
        if (
            donor.key.fingerprint != fingerprint
            or donor.differentiable != differentiable
            or donor.key.resident != use_resident
            or donor.key.tuned != tuned_token
            or donor.n_layers != len(weights)
        ):
            raise ValueError(
                "donor plan does not match this stack's plan key "
                "(fingerprint / differentiable / residency / tuned / layers)"
            )
        route = donor.route
        exec_weights = list(donor.weights)
        exec_biases = donor.biases
        out_gather = donor.out_gather
        layer_plans = [
            dataclasses.replace(
                lp,
                grid_steps=_cost.layer_grid_steps(
                    ew, width, block_n=t_block_n
                ),
            )
            for lp, ew in zip(donor.layers, exec_weights)
        ]
    else:
        fused_family = route in (
            _routes.ROUTE_FUSED,
            _routes.ROUTE_FUSED_TILED,
        )
        exec_weights = []
        exec_biases = []
        comps = []  # each layer's ComponentLayout, or None
        layer_plans = []
        # A stack may repeat one weight object (RadiX-net cycles a few
        # phase matrices through 120 layers): re-lay each object once,
        # so the device holds one copy per object, not one per layer.
        relaid: dict[int, tuple] = {}
        row_biases: dict[tuple[int, int], Array] = {}
        for i, (w, b) in enumerate(zip(weights, biases)):
            src_layout = _layout.layer_layout(w)
            ew, comp = w, None
            if not fused_family and relayout and id(w) not in relaid:
                if t_layout is None and t_block_size is None:
                    # A tuned layout or block size wins over it.
                    comp = _components.component_layout(w)
                if comp is not None:
                    ew = comp.weight
                elif t_layout is not None:
                    ew = _force_layout(w, t_layout)
                else:
                    ew = _layout.to_preferred_layout(w)
                if t_block_size is not None:
                    bs = getattr(ew, "block_shape", (t_block_size,))[0]
                    if bs != t_block_size:
                        ew = _reblock(ew, t_block_size)
                relaid[id(w)] = (ew, comp)
            if not fused_family and relayout:
                ew, comp = relaid[id(w)]
            if comp is not None:
                # The bias follows the layer's rows: b[R].
                if (id(w), id(b)) not in row_biases:
                    row_biases[id(w), id(b)] = jnp.asarray(
                        np.asarray(jax.device_get(b))[comp.rows]
                    )
                b = row_biases[id(w), id(b)]
            exec_layout = (
                "component" if comp is not None else _layout.layer_layout(ew)
            )
            path = (
                route
                if fused_family
                else _routes.layer_path(ew, differentiable=differentiable)
            )
            tp = None
            if differentiable and isinstance(ew, BlockCSRMatrix):
                # The one and only topology sort for this layer: every
                # backward of every step — at every width class, via
                # donor sharing — reuses this plan's permutation.
                tp = ew.transpose_plan()
            exec_weights.append(ew)
            exec_biases.append(b)
            comps.append(comp)
            layer_plans.append(
                LayerPlan(
                    index=i,
                    source_layout=src_layout,
                    layout=exec_layout,
                    path=path,
                    grid_steps=_cost.layer_grid_steps(
                        ew, width, block_n=t_block_n
                    ),
                    transpose_plan=tp,
                )
            )
        layer_gathers, out_gather = _row_gathers(comps)
        layer_plans = [
            dataclasses.replace(lp, gather=g)
            for lp, g in zip(layer_plans, layer_gathers)
        ]
        if route == _routes.ROUTE_LAYERED and all(
            lp.path == "xla-dense" for lp in layer_plans
        ):
            route = _routes.ROUTE_XLA

    plan = StackPlan(
        key=PlanKey(
            fingerprint, width, differentiable, use_resident, tuned=tuned_token
        ),
        route=route,
        layers=tuple(layer_plans),
        width=width,
        differentiable=differentiable,
        grid_steps=sum(lp.grid_steps for lp in layer_plans),
        weights=tuple(exec_weights),
        biases=tuple(exec_biases),
        source_weights=weights,
        source_biases=biases,
        tuned=tuned,
        out_gather=out_gather,
    )
    if plan.is_fused_route:
        if donor is not None:
            plan._stacked = donor._stacked  # one device copy per topology
        else:
            from repro.core import dnn as _dnn

            plan._stacked = (
                _dnn.stack_bsr(list(exec_weights)),
                jnp.stack(list(biases)),
            )
    plan._fn = _make_executable(plan)
    return plan
