"""Compile-once execution plans (`docs/architecture.md`).

The paper's economics — inference cost ∝ stored nonzeros — assume the
per-topology analysis is free. It is, but only if it happens once: this
package compiles a sparse stack's layout choices, route (layered /
XLA / sharded), exact grid-step bill, cached block-CSR backward
transpose, and a per-width-class jitted executable into a
:class:`StackPlan`, cached in a :class:`PlanCache` keyed by
``(topology fingerprint, width class, differentiable?)``. Every
execution path — ``repro.core.dnn``, ``repro.serve``, ``repro.train``
— consults plans instead of re-deriving dispatch per call.
"""

from repro.plan.cache import (  # noqa: F401
    PlanCache,
    default_cache,
    reset_default_cache,
)
from repro.plan.cost import (  # noqa: F401
    layer_block_area,
    layer_grid_steps,
    mxv_grid_steps,
    stack_block_work,
    stack_grid_steps,
)
from repro.plan.degrade import (  # noqa: F401
    LEVEL_LAYERED,
    LEVEL_SHARDED,
    DegradationLadder,
    DegradeEvent,
)
from repro.plan.layout import (  # noqa: F401
    ELL_WASTE_THRESHOLD,
    layer_layout,
    preferred_layout,
    to_preferred_layout,
)
from repro.plan.mxm import (  # noqa: F401
    MxmPlan,
    mxm_cache_stats,
    mxm_plan,
    reset_mxm_cache,
)
from repro.plan.routes import (  # noqa: F401
    ROUTE_LAYERED,
    ROUTE_SHARDED,
    ROUTE_XLA,
    layer_path,
)
from repro.plan.sharded import (  # noqa: F401
    ShardedLayerPlan,
    ShardedStackPlan,
    build_sharded_plan,
    mesh_fingerprint,
)
from repro.plan.stack_plan import (  # noqa: F401
    DEFAULT_WIDTH_CLASSES,
    LayerPlan,
    PlanKey,
    StackPlan,
    build_plan,
    quantize_width,
    topology_fingerprint,
)

__all__ = [
    "ELL_WASTE_THRESHOLD",
    "DEFAULT_WIDTH_CLASSES",
    "ROUTE_LAYERED",
    "ROUTE_SHARDED",
    "ROUTE_XLA",
    "LEVEL_LAYERED",
    "LEVEL_SHARDED",
    "DegradationLadder",
    "DegradeEvent",
    "LayerPlan",
    "PlanCache",
    "PlanKey",
    "ShardedLayerPlan",
    "ShardedStackPlan",
    "StackPlan",
    "build_plan",
    "build_sharded_plan",
    "default_cache",
    "layer_block_area",
    "layer_grid_steps",
    "layer_layout",
    "stack_block_work",
    "layer_path",
    "mesh_fingerprint",
    "preferred_layout",
    "quantize_width",
    "reset_default_cache",
    "stack_grid_steps",
    "to_preferred_layout",
    "topology_fingerprint",
]
