"""LRU cache of compiled :class:`~repro.plan.StackPlan` objects.

The cache is what turns per-call analysis into per-topology analysis:
serving looks a plan up per dispatched panel, and after the first panel
of each width class every lookup is a hit — zero layout decisions, zero
grid-step sums, zero topology sorts, zero recompiles on the hot path.

Keying: ``(topology fingerprint, width class, differentiable?, mesh,
tuning)`` — see :class:`repro.plan.PlanKey`. Because plans
bind weight/bias VALUES (serving weights are frozen), a hit additionally
requires the cached plan's bound arrays to be the same objects the
caller passed; a same-topology stack with different value arrays
rebuilds instead of silently serving stale numbers.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Sequence

from repro import spans
from repro.plan import sharded as _sharded
from repro.plan.layout import Weight
from repro.plan.stack_plan import (
    PlanKey,
    StackPlan,
    build_plan,
    topology_fingerprint,
)


class PlanCache:
    """Bounded LRU plan cache with observable hit/miss/eviction stats."""

    def __init__(self, max_size: int = 16):
        if max_size < 1:
            raise ValueError("max_size must be >= 1")
        self.max_size = max_size
        self._entries: "OrderedDict[PlanKey, StackPlan]" = OrderedDict()
        self.lookups = 0
        self.hits = 0
        self.misses = 0
        self.builds = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: PlanKey) -> bool:
        return key in self._entries

    @property
    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def stats(self) -> dict:
        return {
            "size": len(self._entries),
            "max_size": self.max_size,
            "lookups": self.lookups,
            "hits": self.hits,
            "misses": self.misses,
            "builds": self.builds,
            "evictions": self.evictions,
            "hit_rate": self.hit_rate,
        }

    def get(
        self,
        weights: Sequence[Weight],
        biases,
        width: int,
        *,
        differentiable: bool = False,
        relayout: bool | None = None,
        fingerprint: str | None = None,
        mesh=None,
        tuned=None,
    ) -> StackPlan:
        """The plan for this (stack, width, differentiable?, mesh) —
        cached.

        ``fingerprint`` skips the host-side topology hash when the
        caller already knows it (the engine computes it once at
        construction). ``mesh`` routes to a mesh-sharded
        :class:`repro.plan.ShardedStackPlan`; its fingerprint lands in
        the :class:`PlanKey`, so a sharded and an unsharded plan for the
        same topology never collide. ``tuned`` (a
        ``repro.tune.TunedConfig``) keys the entry by its ``token()``,
        so a tuned and an untuned plan for the same topology never
        collide either; the sharded builder takes no tuning knobs, so
        mesh + tuned together is an error.
        """
        weights = tuple(weights)
        biases = tuple(biases)
        if mesh is not None and tuned is not None:
            raise ValueError(
                "tuned configs apply to single-device plans only; "
                "pass tuned=None with a mesh"
            )
        if fingerprint is None:
            fingerprint = topology_fingerprint(weights)
        mesh_fp = None if mesh is None else _sharded.mesh_fingerprint(mesh)
        tuned_token = None if tuned is None else tuned.token()
        key = PlanKey(
            fingerprint, width, differentiable, mesh=mesh_fp, tuned=tuned_token
        )
        self.lookups += 1
        plan = self._entries.get(key)
        if (
            plan is not None
            and len(plan.source_weights) == len(weights)
            and all(a is b for a, b in zip(plan.source_weights, weights))
            and all(a is b for a, b in zip(plan.source_biases, biases))
        ):
            self.hits += 1
            self._entries.move_to_end(key)
            return plan
        self.misses += 1
        # A cached plan for the same stack at ANOTHER width class
        # donates its width-independent artifacts (relayouted weights
        # and biases, row gathers, cached transposes; for sharded plans:
        # partition layouts and per-shard transposes) — only the
        # executable and the grid-step bills are per-width.
        donor = None
        for cand in reversed(self._entries.values()):
            if (
                cand.key.fingerprint == fingerprint
                and cand.differentiable == differentiable
                and cand.key.mesh == mesh_fp
                and cand.key.tuned == tuned_token
                and len(cand.source_weights) == len(weights)
                and all(
                    a is b for a, b in zip(cand.source_weights, weights)
                )
                and all(a is b for a, b in zip(cand.source_biases, biases))
            ):
                donor = cand
                break
        with spans.span("plan.build", width=width) as s:
            if mesh is not None:
                plan = _sharded.build_sharded_plan(
                    weights,
                    biases,
                    width,
                    mesh,
                    differentiable=differentiable,
                    fingerprint=fingerprint,
                    donor=donor,
                )
            else:
                plan = build_plan(
                    weights,
                    biases,
                    width,
                    differentiable=differentiable,
                    relayout=relayout,
                    fingerprint=fingerprint,
                    donor=donor,
                    tuned=tuned,
                )
            s.set(
                route=plan.route,
                component_layers=0 if mesh is not None else plan.component_layers,
                weight_args=(
                    len(plan.weights) if mesh is not None else plan.weight_args
                ),
            )
        self.builds += 1
        self._entries[key] = plan
        self._entries.move_to_end(key)
        while len(self._entries) > self.max_size:
            self._entries.popitem(last=False)
            self.evictions += 1
        return plan

    def plans(self) -> list:
        """The cached plans, least recently used first (no LRU touch)."""
        return list(self._entries.values())

    def clear(self) -> None:
        self._entries.clear()


# Shared cache behind the module-level convenience wrappers
# (repro.core.dnn.dnn_forward with a mesh). Engines own their
# own caches; this one serves ad-hoc functional callers. Plans hold
# strong references to the weight stacks they bind, so this cache is
# kept SMALL — loops over many transient models retain at most
# ``max_size`` stacks; call ``default_cache().clear()`` to drop them
# eagerly.
_DEFAULT_CACHE: PlanCache | None = None


def default_cache() -> PlanCache:
    global _DEFAULT_CACHE
    if _DEFAULT_CACHE is None:
        _DEFAULT_CACHE = PlanCache(max_size=4)
    return _DEFAULT_CACHE


def reset_default_cache() -> None:
    """Drop the shared default cache (entries AND stats) — test
    isolation: a test asserting hit/miss/build counts must not inherit
    plans another test parked in the process-wide cache."""
    global _DEFAULT_CACHE
    _DEFAULT_CACHE = None
