"""The deterministic kernel-config sweep behind the tuning table.

``sweep_stack`` builds one :class:`~repro.plan.StackPlan` per candidate
:class:`~repro.tune.table.TunedConfig`, scores each with the exact cost
model of ``repro.plan.cost``, gates numerics against the default plan's
output, and picks a winner **deterministically**:

1. ``stack_block_work`` — grid steps × stored-block area, summed over
   the plan's executed weights. Block-size-invariant (a re-blocked
   candidate cannot win by coarsening the grid) and layout-sensitive
   (forcing block-CSR on a skewed stack genuinely drops the bill).
2. enumeration order — the default config is enumerated first, so a
   candidate must *strictly* improve the bill to displace it.

Wall-clock is measured (min over ``reps`` timed forwards, recorded in
the sweep evidence and the table entry) but **never used for
selection** — CI machines jitter, cost models do not, and a tuning
table that flips winners run-to-run is worse than no table.

Accuracy is a hard gate, not a score: every candidate's probe output
must stay within ``accuracy_rtol × max|default output|`` of the default
plan's output, so a re-blocked or re-laid config can only be selected
if its numerics hold on this topology.
"""

from __future__ import annotations

import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import DEFAULT_BLOCK_N
from repro.tune.table import TunedConfig, TuningTable

def default_candidates(
    *,
    layouts: Sequence[str | None] = (None, "ell", "bcsr"),
    block_ns: Sequence[int | None] = (None,),
    block_sizes: Sequence[int | None] = (None,),
) -> list[TunedConfig]:
    """The sweep's candidate grid — the all-``None`` default config is
    always enumerated first (ties go to it)."""
    out: list[TunedConfig] = []
    seen: set[str] = set()
    for bn in block_ns:
        for lay in layouts:
            for bs in block_sizes:
                cfg = TunedConfig(block_size=bs, block_n=bn, layout=lay)
                if cfg.token() in seen:
                    continue
                seen.add(cfg.token())
                out.append(cfg)
    out.sort(key=lambda c: not c.is_default)  # stable: default first
    return out


def _probe_panel(weights, width: int) -> jax.Array:
    k = weights[0].shape[1]
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.standard_normal((k, width)), jnp.float32)


def _timed_forward(plan, probe, reps: int) -> float:
    jax.block_until_ready(plan.forward(probe))  # compile outside the clock
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(plan.forward(probe))
        best = min(best, time.perf_counter() - t0)
    return best


def sweep_stack(
    weights,
    biases,
    width: int,
    *,
    candidates: Sequence[TunedConfig] | None = None,
    reps: int = 2,
    accuracy_rtol: float = 0.02,
    time_forwards: bool = True,
    probe=None,
) -> tuple[TunedConfig, list[dict]]:
    """Sweep candidate configs over one stack; return (winner, records).

    One record per candidate: ``token``, ``config``, ``route``,
    ``grid_steps``, ``block_work``, ``wall_s``,
    ``max_abs_err``, ``ok`` (accuracy gate), ``selected``. Candidates
    whose plan fails to build are recorded with ``error`` and skipped.
    ``time_forwards=False`` skips the timed reps (pure cost-model sweep
    — what the plan-layer tests use to stay fast). ``probe`` overrides
    the default seeded random-normal probe panel — pass workload-shaped
    inputs (e.g. the GraphChallenge {0,1} panels) so the accuracy gate
    judges the numerics that will actually be served.
    """
    from repro import plan as _plan

    if candidates is None:
        candidates = default_candidates()
    candidates = list(candidates)
    if not any(c.is_default for c in candidates):
        # The default config is the accuracy reference and the evidence
        # baseline — a custom candidate list always competes against it.
        candidates.insert(0, TunedConfig())
    weights = tuple(weights)
    biases = tuple(biases)
    if probe is None:
        probe = _probe_panel(weights, width)

    default_plan = _plan.build_plan(weights, biases, width)
    ref = np.asarray(default_plan.forward(probe), np.float32)
    err_bound = accuracy_rtol * max(float(np.max(np.abs(ref))), 1e-6)

    records: list[dict] = []
    best_idx: int | None = None
    best_score: tuple | None = None
    for idx, cand in enumerate(candidates):
        rec: dict = {"token": cand.token(), "config": cand.to_dict()}
        try:
            plan = (
                default_plan
                if cand.is_default
                else _plan.build_plan(weights, biases, width, tuned=cand)
            )
        except Exception as e:  # noqa: BLE001 — a bad knob combo skips
            rec.update(error=f"{type(e).__name__}: {e}", ok=False)
            records.append(rec)
            continue
        bn = cand.block_n or DEFAULT_BLOCK_N
        block_work = _plan.stack_block_work(plan.weights, width, block_n=bn)
        out = np.asarray(plan.forward(probe), np.float32)
        err = float(np.max(np.abs(out - ref)))
        ok = err <= err_bound
        rec.update(
            route=plan.route,
            grid_steps=int(plan.grid_steps),
            block_work=int(block_work),
            max_abs_err=err,
            ok=ok,
        )
        if time_forwards:
            rec["wall_s"] = _timed_forward(plan, probe, reps)
        records.append(rec)
        if not ok:
            continue
        score = (block_work, idx)
        if best_score is None or score < best_score:
            best_score = score
            best_idx = idx
    if best_idx is None:
        raise RuntimeError(
            "tuning sweep found no candidate passing the accuracy gate "
            "(the default config should always pass — bad probe?)"
        )
    for i, rec in enumerate(records):
        rec["selected"] = i == best_idx
    return candidates[best_idx], records


def tune_stack(
    weights,
    biases,
    width: int,
    *,
    table: TuningTable | None = None,
    backend: str | None = None,
    dtype: str | None = None,
    fingerprint: str | None = None,
    sweep: tuple[TunedConfig, list] | None = None,
    **sweep_kw,
) -> tuple[TunedConfig, TuningTable]:
    """Sweep one stack and record the winner in a tuning table.

    Returns ``(winner, table)``. The entry's evidence carries the tuned
    and default bills side by side so a committed table is auditable:
    the bench gate re-checks ``grid_steps <= default_grid_steps`` from
    the file alone. ``sweep`` reuses a prior :func:`sweep_stack` result
    (the bench sweeps once and both reports and records it).
    """
    from repro import plan as _plan

    if table is None:
        table = TuningTable()
    if backend is None:
        backend = jax.default_backend()
    if dtype is None:
        dtype = str(np.dtype(weights[0].dtype))
    if fingerprint is None:
        fingerprint = _plan.topology_fingerprint(weights)
    if sweep is None:
        winner, records = sweep_stack(weights, biases, width, **sweep_kw)
    else:
        winner, records = sweep
    default_rec = next(r for r in records if r["token"] == "default")
    winner_rec = next(r for r in records if r.get("selected"))
    evidence = {
        "width": width,
        "route": winner_rec["route"],
        "default_route": default_rec["route"],
        "grid_steps": winner_rec["grid_steps"],
        "default_grid_steps": default_rec["grid_steps"],
        "block_work": winner_rec["block_work"],
        "default_block_work": default_rec["block_work"],
        "max_abs_err": winner_rec["max_abs_err"],
        "candidates": len(records),
    }
    if "wall_s" in winner_rec:
        evidence["wall_s"] = winner_rec["wall_s"]
        evidence["default_wall_s"] = default_rec["wall_s"]
    table.put(fingerprint, backend, dtype, winner, evidence)
    return winner, table
