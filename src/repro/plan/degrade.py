"""Graceful plan degradation: sharded → single-device layered.

A :class:`repro.plan.PlanCache` answers "the plan for this (stack,
width)"; this module answers "the plan for this (stack, width) *given
the world is partly broken*". The ladder orders the execution levels a
serving engine can run a fingerprinted stack at:

1. ``sharded``  — mesh-sharded :class:`~repro.plan.ShardedStackPlan`
   (only when the engine was built with a mesh and it is healthy);
2. ``layered``  — single-device per-layer kernel plan, the floor: it
   needs nothing but one device and always exists.

``get_plan`` walks the ladder top-down and returns the first level that
produces a plan. A level that fails to build — a plan-compile failure
or an injected fault — is marked unhealthy and the walk continues
downward, so **in-flight requests are never dropped**: a
shard failure mid-stream re-plans the same fingerprint on a single
device and the panel that triggered the fallback is still served (the
plan cache already holds or builds the lower-level plan for the same
``PlanKey`` fingerprint). Health marks are sticky until ``restore``
(operator re-slots the node), and every transition is recorded in
:attr:`DegradationLadder.events` for the serve-stats surface.

The ladder deliberately knows nothing about *why* a level failed —
fault injection lives in ``repro.testing.faults`` and reaches this
layer only through the engine's ``compile_hook`` callback, keeping
``repro.plan`` dependency-free of the testing harness.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

LEVEL_SHARDED = "sharded"
LEVEL_LAYERED = "layered"


@dataclasses.dataclass(frozen=True)
class DegradeEvent:
    """One ladder transition (demotion or restore)."""

    step: int  # engine dispatch ordinal when the transition happened
    level: str  # the level whose health changed
    healthy: bool  # False = demoted, True = restored
    reason: str


class DegradationLadder:
    """Health-aware plan lookup over a :class:`~repro.plan.PlanCache`.

    ``mesh`` decides the engine's *preferred* level; the ladder serves
    the highest healthy level at or below it. The
    floor level (``layered``) cannot be demoted — a failure there
    propagates, because there is nothing left to degrade to.
    """

    def __init__(self, cache, *, mesh=None, tuned=None):
        self.cache = cache
        self.mesh = mesh
        # Tuned kernel config (repro.tune.TunedConfig) applied at the
        # single-device levels; the sharded builder takes no tuning
        # knobs, so a sharded lookup always passes tuned=None.
        self.tuned = tuned
        self._healthy = {LEVEL_SHARDED: True}
        self.events: list[DegradeEvent] = []

    @property
    def preferred_level(self) -> str:
        return LEVEL_SHARDED if self.mesh is not None else LEVEL_LAYERED

    def levels(self) -> list[str]:
        """Currently serviceable levels, most preferred first."""
        out = []
        if self.mesh is not None and self._healthy[LEVEL_SHARDED]:
            out.append(LEVEL_SHARDED)
        out.append(LEVEL_LAYERED)
        return out

    def is_healthy(self, level: str) -> bool:
        return self._healthy.get(level, True)

    @property
    def degraded(self) -> bool:
        return self.levels()[0] != self.preferred_level

    def mark_unhealthy(self, level: str, *, reason: str, step: int = -1) -> None:
        """Demote a level (e.g. the mesh lost a shard). Idempotent."""
        if level not in self._healthy:
            raise ValueError(
                f"level {level!r} cannot be demoted (floor or unknown)"
            )
        if self._healthy[level]:
            self._healthy[level] = False
            self.events.append(DegradeEvent(step, level, False, reason))

    def restore(self, level: str, *, reason: str = "restored", step: int = -1):
        """Re-admit a demoted level (operator re-slotted the node)."""
        if level not in self._healthy:
            raise ValueError(f"level {level!r} has no health state")
        if not self._healthy[level]:
            self._healthy[level] = True
            self.events.append(DegradeEvent(step, level, True, reason))

    def get_plan(
        self,
        weights,
        biases,
        width: int,
        *,
        differentiable: bool = False,
        fingerprint: str | None = None,
        step: int = -1,
        compile_hook: Callable[[str], None] | None = None,
    ):
        """(plan, level, cache_hit) at the best healthy level.

        ``compile_hook(level)`` runs before each level's cache lookup;
        raising from it (fault injection) demotes that
        level and falls through. Only the floor's failure propagates.
        """
        last_err: Exception | None = None
        for level in self.levels():
            try:
                if compile_hook is not None:
                    compile_hook(level)
                before = self.cache.hits
                plan = self.cache.get(
                    weights,
                    biases,
                    width,
                    differentiable=differentiable,
                    fingerprint=fingerprint,
                    mesh=self.mesh if level == LEVEL_SHARDED else None,
                    tuned=self.tuned if level != LEVEL_SHARDED else None,
                )
                return plan, level, self.cache.hits > before
            except Exception as e:  # noqa: BLE001 — any build/compile fault
                last_err = e
                if level == LEVEL_LAYERED:
                    raise
                self.mark_unhealthy(
                    level,
                    reason=f"{type(e).__name__}: {e}",
                    step=step,
                )
        raise last_err if last_err else RuntimeError("no serviceable level")

    def describe(self) -> dict:
        return {
            "preferred": self.preferred_level,
            "current": self.levels()[0],
            "degraded": self.degraded,
            "health": dict(self._healthy),
            "events": [dataclasses.asdict(e) for e in self.events],
        }
