"""Compile-once plans for GraphBLAS ``mxm``/``mxv`` on sparse layouts.

``repro.core.graphblas`` routes every sparse × dense product through
here instead of the pure-jnp XLA oracles (``repro.sparse.ops``): the
plan binds the occupancy-optimal execution layout (the same ELL-waste
heuristic DNN stack plans apply — a skewed ELL operand is re-laid out
to block-CSR once, at plan build), the exact grid-step bill from the
cost model (narrow ``mxv`` panels billed at the effective 8-wide tile,
not a full ``DEFAULT_BLOCK_N`` tile), and the Pallas kernel wrapper for
the plan's semiring.

Plans are cached under the same :class:`~repro.plan.stack_plan.PlanKey`
the stack-plan cache uses, with the key's ``semiring`` field carrying
the ⊕.⊗ algebra — a ``plus_times`` and a ``min_plus`` plan over the
same adjacency can never collide. Value staleness follows the
``PlanCache`` convention: a plan is only reused for the *same* operand
object (identity check), because the fingerprint hashes topology, not
stored values.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Union

import jax

from repro.kernels import DEFAULT_BLOCK_N
from repro.kernels import ops as kernel_ops
from repro.plan.cost import layer_grid_steps
from repro.plan.layout import layer_layout, to_preferred_layout
from repro.plan.stack_plan import PlanKey, topology_fingerprint
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix

Array = jax.Array
SparseMatrix = Union[BlockSparseMatrix, BlockCSRMatrix]

_MAX_PLANS = 32


@dataclasses.dataclass
class MxmPlan:
    """One sparse operand × one panel width × one semiring, compiled.

    ``grid_steps`` is the exact Pallas bill of the kernel route;
    ``xla_equiv_grid_steps`` is the occupancy-equivalent bill of the
    *source* layout — what the pre-plan XLA sparse path pays (the ELL
    einsum computes every ``nrb × max_blocks_per_row`` slot, padding
    included), which is the number the GNN bench arm beats.
    """

    key: PlanKey
    source_layout: str  # caller's layout ("ell" / "bcsr")
    layout: str  # execution layout after the waste heuristic
    width: int  # the exact panel width n this plan bills for
    grid_steps: int  # kernel-route bill at this width (cost model)
    xla_equiv_grid_steps: int  # source-layout bill (XLA sparse path)
    weight: SparseMatrix  # execution operand (possibly re-laid-out)
    source: SparseMatrix  # the operand the plan was built from
    _fn: Callable[[SparseMatrix, Array], Array]

    def __call__(self, b: Array) -> Array:
        return self._fn(self.weight, b)


_cache: OrderedDict[PlanKey, MxmPlan] = OrderedDict()
_stats = {"lookups": 0, "hits": 0, "builds": 0, "evictions": 0}


def mxm_cache_stats() -> dict:
    return dict(_stats)


def reset_mxm_cache() -> None:
    _cache.clear()
    for k in _stats:
        _stats[k] = 0


def _executable(layout: str, semiring_name: str):
    if layout == "bcsr":
        return lambda w, b: kernel_ops.bcsr_spmm(
            w, b, semiring_name=semiring_name
        )
    return lambda w, b: kernel_ops.bsr_spmm(w, b, semiring_name=semiring_name)


def _build(
    a: SparseMatrix, n: int, semiring_name: str, key: PlanKey, block_n: int
) -> MxmPlan:
    exec_w = to_preferred_layout(a)  # ELL→CSR once the pad waste crosses
    return MxmPlan(
        key=key,
        source_layout=layer_layout(a),
        layout=layer_layout(exec_w),
        width=n,
        grid_steps=layer_grid_steps(exec_w, n, block_n=block_n),
        xla_equiv_grid_steps=layer_grid_steps(a, n, block_n=block_n),
        weight=exec_w,
        source=a,
        _fn=_executable(layer_layout(exec_w), semiring_name),
    )


def mxm_plan(
    a: SparseMatrix,
    n: int,
    semiring_name: str = "plus_times",
    *,
    block_n: int = DEFAULT_BLOCK_N,
) -> MxmPlan:
    """The cached plan for ``a ⊕.⊗ B`` with an ``(·, n)`` dense panel.

    Keyed by (topology fingerprint, exact width n, semiring) — the width
    is NOT quantized, so narrow ``mxv`` panels (n = 1) are billed at the
    8-wide effective tile the kernels actually run. A key hit whose
    cached plan was built from a *different* operand object rebuilds
    (values may differ under the same topology fingerprint).
    """
    key = PlanKey(
        fingerprint=topology_fingerprint([a]),
        width=n,
        differentiable=False,
        semiring=semiring_name,
    )
    _stats["lookups"] += 1
    plan = _cache.get(key)
    if plan is not None and plan.source is a:
        _stats["hits"] += 1
        _cache.move_to_end(key)
        return plan
    plan = _build(a, n, semiring_name, key, block_n)
    _stats["builds"] += 1
    _cache[key] = plan
    _cache.move_to_end(key)
    while len(_cache) > _MAX_PLANS:
        _cache.popitem(last=False)
        _stats["evictions"] += 1
    return plan
