"""Deterministic kernel autotuner + cached tuning table (`docs/tuning.md`).

Per ``(topology fingerprint, backend, dtype)`` this package sweeps the
kernel knobs the plan layer exposes — column-tile width ``block_n``,
weight block size and forced layout (ELL vs block-CSR) — scores
candidates with the exact grid-step cost model (``repro.plan.cost``),
and persists the winner in a versioned on-disk :class:`TuningTable` that
``repro.plan.PlanCache`` / ``build_plan`` consult before falling back
to defaults. Selection is cost-model-deterministic; wall-clock is
recorded as evidence, never used to pick (CI machines jitter, cost
models do not).
"""

from repro.tune.sweep import (  # noqa: F401
    default_candidates,
    sweep_stack,
    tune_stack,
)
from repro.tune.table import (  # noqa: F401
    SCHEMA_VERSION,
    TunedConfig,
    TuningTable,
    TuningTableError,
    entry_key,
)

__all__ = [
    "SCHEMA_VERSION",
    "TunedConfig",
    "TuningTable",
    "TuningTableError",
    "default_candidates",
    "entry_key",
    "sweep_stack",
    "tune_stack",
]
