"""CI perf-regression gate over ``BENCH_kernels.json``.

Compares a fresh benchmark artifact against the committed baseline
(``benchmarks/baselines/BENCH_kernels.baseline.json``) and FAILS the job
with a readable diff table instead of merely uploading the artifact.

Three classes of check, matched to what each field can promise:

* **exact** — grid-step counts, pallas_call counts, layouts, and the
  serve arm's deterministic accounting (rows, padded slots, engine
  steps, latency). These are hardware-independent architecture truth:
  any drift is a real behaviour change and fails the gate.
* **tolerant** — wall-clock fields. Runner noise dominates, so the gate
  only rejects order-of-magnitude blowups (``--tol``, default 25x).
* **non-regression** — the serve arm's continuous-batching pad-slot
  fraction must not exceed the baseline's, and must stay strictly below
  the static arm's (the whole point of the scheduler).

Sections whose generator parameters differ from the baseline (e.g. a
full run compared against the quick baseline) are reported as SKIP, not
failed — the gate only compares like with like. Baseline topologies must
all be present in the fresh artifact (the quick grid is a subset of the
full grid). An arm present in the fresh artifact but ABSENT from the
baseline (a newly added arm, mid-PR) is a warn + SKIP, never a crash:
the gate keeps passing until the baseline is refreshed to cover it.

Run from the repo root:
  PYTHONPATH=src python -m benchmarks.kernel_bench --quick
  python tools/check_bench.py
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
DEFAULT_FRESH = REPO_ROOT / "BENCH_kernels.json"
DEFAULT_BASELINE = (
    REPO_ROOT / "benchmarks" / "baselines" / "BENCH_kernels.baseline.json"
)

# Per-section generator parameters: a section is only compared when ALL
# of these match between baseline and fresh artifact.
PARAMS = {
    "train": ("m", "layers", "block", "blocks_per_row", "n"),
    "serve": (
        "m",
        "layers",
        "blocks_per_row",
        "requests",
        "batch_size",
        "tile_align",
        "min_fill",
        "max_wait",
        "trace",
    ),
    "plan": (
        "m",
        "layers",
        "blocks_per_row",
        "requests",
        "batch_size",
        "tile_align",
        "width_classes",
        "trace",
        "train_params",
    ),
    "sharded": ("m", "layers", "block", "blocks_per_row", "n", "shards"),
    "faults": (
        "m",
        "layers",
        "blocks_per_row",
        "requests",
        "batch_size",
        "tile_align",
        "seed",
    ),
    "challenge": (
        "neurons",
        "layers",
        "n_inputs",
        "panel_width",
        "batch_align",
        "density",
        "seed",
    ),
    "gnn": (
        "m",
        "block",
        "total_blocks",
        "skew",
        "feat_dim",
        "rounds",
        "bf_iters_cap",
        "seed",
    ),
    "tune": ("params",),
    "fleet": (
        "m",
        "layers",
        "blocks_per_row",
        "duration_s",
        "seed",
        "replicas",
        "rate_factors",
        "miss_budget",
        "profile",
        "width_classes",
        "width_mix",
        "deadline_s",
        "service_model",
        "max_pending_cols",
    ),
}

TRAIN_EXACT = (
    "pallas_calls_per_step",
    "pallas_calls_forward_only",
    "grid_steps_forward",
    "grid_steps_backward_kernel",
    "layout_per_layer",
    "weight_cotangent_pattern_preserved",
    "loss_decreased",
)
# Plan arm (compiled execution plans): deterministic accounting checked
# exactly, wall-clocks tolerantly, and the headline amortization gated.
PLAN_SERVE_EXACT = (
    "engine_steps",
    "rows_served",
    "padded_slots",
    "pad_slot_fraction",
    "grid_steps_total",
    "plan_lookups",
    "plan_builds",
    "plan_evictions",
    "cache_hit_rate",
    "recompiles_by_class",
)
PLAN_TRAIN_EXACT = (
    "layout_per_layer",
    "csr_layers",
    "sorts_at_plan_build",
    "sorts_total",
    "legacy_jaxpr_has_sort",
    "planned_jaxpr_has_sort",
    "loss_decreased",
    "losses_match_legacy",
)
TOPOLOGY_EXACT = (
    "grid_steps_ell",
    "grid_steps_csr",
    "max_blocks_per_row",
    "mean_blocks_per_row",
)
# Sharding arm (balanced block-CSR partitioner): all host-side
# deterministic accounting — per-shard nnz/bills, the bill-equality
# invariant, and the load-imbalance factor are checked exactly.
SHARDED_EXACT = (
    "nnz_blocks_total",
    "nnz_per_shard",
    "grid_steps_unsharded",
    "grid_steps_per_shard",
    "grid_steps_sharded_total",
    "shard_pad_blocks",
    "bill_matches_unsharded",
    "imbalance",
    "critical_path_steps",
    "parallel_speedup_bound",
)
# Robustness arm (fault injection + graceful degradation): every fault
# is SCHEDULED, so the whole faulted run is deterministic — loss
# buckets, goodput, degradation levels and the train replay are all
# checked exactly; wall-clock tolerantly. New fields get warn+SKIP
# against older baselines (same convention as plan/sharded).
FAULTS_SERVE_EXACT = (
    "completed",
    "engine_steps",
    "deadline_misses",
    "goodput",
    "faults",
    "shed_fraction",
    "injector_fired",
    "injector_pending",
)
FAULTS_DEGRADE_EXACT = (
    "levels",
    "recovery_steps",
    "matches_single_device_after_failure",
    "ladder_events",
    "degraded",
)
FAULTS_TRAIN_EXACT = (
    "steps",
    "skipped_steps",
    "restarts",
    "losses_match_clean",
    "loss_decreased",
)
# Challenge arm (GraphChallenge workload): the topology, routing, and
# the answer set are all deterministic given the generator params —
# checked exactly; the official edges×inputs/sec rate rides on
# wall-clock and is only gated against blowups.
CHALLENGE_EXACT = (
    "bias",
    "fan_in",
    "edges",
    "routes",
    "levels",
    "width_classes",
    "engine_steps",
    "served",
    "grid_steps",
    "n_categories",
    "reference_match",
)
# GNN arm (semiring-kernel routing): layouts, grid-step bills,
# pallas_call counts, plan-cache traffic, and the Bellman-Ford fixpoint
# are pure functions of the seeded topology — checked exactly; the
# convolution's scale-normalized error float rides on the runner's
# accumulation order and is gated via the conv_matches_oracle bool.
GNN_EXACT = (
    "source_layout",
    "exec_layout",
    "kernel_grid_steps",
    "xla_sparse_grid_steps",
    "mxv_grid_steps",
    "pallas_calls_conv",
    "pallas_calls_oracle",
    "conv_matches_oracle",
    "conv_plan_builds",
    "conv_plan_hits",
    "bf_iters",
    "bf_converged",
    "bf_reachable",
    "bf_matches_numpy",
    "bf_plan_hits",
)
# Tune arm (autotuner sweep): winners, routes, and the cost-model bills
# are pure functions of the generator params — checked exactly; probe
# numerics (max-abs-err floats) and wall-clocks ride on the runner and
# are gated via headline invariants / the time tolerance instead.
TUNE_SKEWED_EXACT = (
    "winner",
    "route_tuned",
    "route_default",
    "grid_steps_tuned",
    "grid_steps_default",
    "block_work_tuned",
    "block_work_default",
    "accuracy_ok",
)
# Fleet arm (replicated serving on a virtual clock): every curve point
# is a pure function of the generator config — latencies, miss rates,
# throughput and routing/plan-cache accounting are all checked exactly;
# only the arm's own wall_time_s (real compute time of the sweep) is
# gated tolerantly.
FLEET_POINT_EXACT = (
    "offered_jobs",
    "served_jobs",
    "failed_jobs",
    "rejected_jobs",
    "deadline_misses",
    "miss_rate",
    "latency_p50_s",
    "latency_p99_s",
    "latency_max_s",
    "throughput_cols_per_s",
    "goodput_cols_per_s",
    "plan_hit_rate",
    "cross_replica_compiles",
    "routing",
)
# Deterministic serve accounting, checked exactly for BOTH arms.
SERVE_EXACT = (
    "requests",
    "engine_steps",
    "rows_served",
    "padded_slots",
    "grid_steps_total",
    "latency_mean",
    "latency_p50",
    "latency_max",
    "deadline_misses",
)


class Gate:
    def __init__(self, tol: float):
        self.tol = tol
        self.rows: list[tuple[str, str, str, str, str]] = []
        self.failed = 0

    def _add(self, section, field, base, fresh, verdict):
        self.rows.append(
            (section, field, _fmt(base), _fmt(fresh), verdict)
        )
        if verdict == "FAIL":
            self.failed += 1

    def exact(self, section, field, base, fresh):
        ok = (
            math.isclose(base, fresh, rel_tol=1e-9, abs_tol=1e-12)
            if isinstance(base, float) or isinstance(fresh, float)
            else base == fresh
        )
        self._add(section, field, base, fresh, "ok" if ok else "FAIL")

    def time(self, section, field, base, fresh):
        ok = fresh <= base * self.tol
        self._add(
            section, field, base, fresh, "ok" if ok else "FAIL"
        )

    def no_worse(self, section, field, base, fresh, eps=1e-9):
        ok = fresh <= base + eps
        self._add(section, field, base, fresh, "ok" if ok else "FAIL")

    def skip(self, section, reason):
        self.rows.append((section, reason, "-", "-", "SKIP"))

    def missing(self, section, what):
        self._add(section, what, "present", "missing", "FAIL")

    def table(self) -> str:
        header = ("section", "field", "baseline", "fresh", "verdict")
        rows = [header, *self.rows]
        widths = [max(len(r[i]) for r in rows) for i in range(5)]
        lines = []
        for j, r in enumerate(rows):
            lines.append(
                "  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip()
            )
            if j == 0:
                lines.append("  ".join("-" * w for w in widths))
        return "\n".join(lines)


def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    s = str(v)
    return s if len(s) <= 32 else s[:29] + "..."


def _topo_key(t: dict) -> tuple:
    return (t["m"], t["block"], t["n"], t["nnz_blocks"], t["skew"])


def _params_match(section: str, base: dict, fresh: dict) -> bool:
    return all(base.get(k) == fresh.get(k) for k in PARAMS[section])


def _section_pair(gate: Gate, section: str, baseline: dict, fresh: dict):
    """(baseline_arm, fresh_arm) when comparable, else None.

    A fresh arm with no baseline counterpart is a newly added arm: warn
    and SKIP so adding an arm never breaks the gate mid-PR (refresh the
    baseline to start gating it). A baseline arm MISSING from the fresh
    artifact is a real regression and fails.
    """
    bs, fs = baseline.get(section), fresh.get(section)
    if bs is None:
        if fs is not None:
            gate.skip(section, "absent from baseline (new arm?)")
            print(
                f"warning: section {section!r} is in the fresh artifact "
                "but not the baseline — skipping; refresh the baseline "
                "to gate it",
                file=sys.stderr,
            )
        return None
    if fs is None:
        gate.missing(section, "section")
        return None
    if not _params_match(section, bs, fs):
        gate.skip(section, "generator params differ from baseline")
        return None
    return bs, fs


def check(baseline: dict, fresh: dict, tol: float) -> Gate:
    gate = Gate(tol)

    # --- topologies: every baseline topology must appear, steps exact --
    fresh_topos = {_topo_key(t): t for t in fresh.get("topologies", [])}
    for bt in baseline.get("topologies", []):
        key = _topo_key(bt)
        name = f"topo m={key[0]} nnz={key[3]} skew={key[4]}"
        ft = fresh_topos.get(key)
        if ft is None:
            gate.missing(name, "topology")
            continue
        for field in TOPOLOGY_EXACT:
            gate.exact(name, field, bt[field], ft[field])
        for arm in ("ell", "csr", "dense"):
            gate.time(
                name,
                f"xla_time_s.{arm}",
                bt["xla_time_s"][arm],
                ft["xla_time_s"][arm],
            )

    # --- train: exact counts when the generator params match ----------
    pair = _section_pair(gate, "train", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for field in TRAIN_EXACT:
            if field not in bs:
                gate.skip("train", f"{field} absent from baseline")
                continue
            if field not in fs:
                gate.missing("train", field)
                continue
            gate.exact("train", field, bs[field], fs[field])
        for field, bt in bs.get("xla_time_s", {}).items():
            ft = fs.get("xla_time_s", {}).get(field)
            if ft is None:
                gate.missing("train", f"xla_time_s.{field}")
                continue
            gate.time("train", f"xla_time_s.{field}", bt, ft)

    # --- plan: compiled-plan amortization (exact) + wall-clocks -------
    pair = _section_pair(gate, "plan", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for sub, fields in (
            ("serve", PLAN_SERVE_EXACT),
            ("train", PLAN_TRAIN_EXACT),
        ):
            for field in fields:
                bv = bs.get(sub, {}).get(field)
                fv = fs.get(sub, {}).get(field)
                if bv is None:
                    # field newer than the committed baseline: warn+skip
                    gate.skip(f"plan.{sub}", f"{field} absent from baseline")
                    continue
                if fv is None:
                    gate.missing(f"plan.{sub}", field)
                    continue
                gate.exact(f"plan.{sub}", field, bv, fv)
        # headline: the cache hit rate must never regress below baseline
        hit_b = bs.get("serve", {}).get("cache_hit_rate")
        hit_f = fs.get("serve", {}).get("cache_hit_rate", 0.0)
        if hit_b is not None:
            gate._add(
                "plan",
                "cache_hit_rate >= baseline",
                hit_b,
                hit_f,
                "ok" if hit_f >= hit_b - 1e-9 else "FAIL",
            )
        wt_b = bs.get("serve", {}).get("wall_time_s")
        wt_f = fs.get("serve", {}).get("wall_time_s")
        if wt_b is not None and wt_f is not None:
            gate.time("plan", "serve.wall_time_s", wt_b, wt_f)
        for arm in ("legacy", "planned"):
            st_b = bs.get("train", {}).get("step_time_s", {}).get(arm)
            st_f = fs.get("train", {}).get("step_time_s", {}).get(arm)
            if st_b is not None and st_f is not None:
                gate.time("plan", f"train.step_time_s.{arm}", st_b, st_f)

    # --- sharded: partitioner accounting, all exact -------------------
    pair = _section_pair(gate, "sharded", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for field in SHARDED_EXACT:
            if field not in bs:
                gate.skip("sharded", f"{field} absent from baseline")
                continue
            if field not in fs:
                gate.missing("sharded", field)
                continue
            gate.exact("sharded", field, bs[field], fs[field])
        # headline invariants hold regardless of baseline drift: the
        # per-shard bills must sum to the unsharded bill and the
        # partitioner must stay within the 10 % imbalance budget
        gate._add(
            "sharded",
            "bills sum to unsharded",
            True,
            fs.get("bill_matches_unsharded", False),
            "ok" if fs.get("bill_matches_unsharded", False) else "FAIL",
        )
        imbalance = fs.get("imbalance")
        if imbalance is None:
            gate.missing("sharded", "imbalance")
        else:
            gate.no_worse("sharded", "imbalance <= 1.10", 1.10, imbalance)

    # --- faults: scheduled-fault determinism + robustness headlines ---
    pair = _section_pair(gate, "faults", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for sub, fields in (
            ("serve", FAULTS_SERVE_EXACT),
            ("degrade", FAULTS_DEGRADE_EXACT),
            ("train", FAULTS_TRAIN_EXACT),
        ):
            for field in fields:
                bv = bs.get(sub, {}).get(field)
                fv = fs.get(sub, {}).get(field)
                if bv is None:
                    gate.skip(f"faults.{sub}", f"{field} absent from baseline")
                    continue
                if fv is None:
                    gate.missing(f"faults.{sub}", field)
                    continue
                gate.exact(f"faults.{sub}", field, bv, fv)
        # headline invariants, gated regardless of baseline drift:
        # goodput holds its floor, shard failure degrades with identical
        # results, and the NaN-lossed train run replays a clean one
        goodput = fs.get("serve", {}).get("goodput")
        if goodput is None:
            gate.missing("faults", "serve.goodput")
        else:
            gate._add(
                "faults",
                "serve.goodput >= 0.8",
                0.8,
                goodput,
                "ok" if goodput >= 0.8 else "FAIL",
            )
        for sub, field in (
            ("degrade", "matches_single_device_after_failure"),
            ("train", "losses_match_clean"),
        ):
            ok = fs.get(sub, {}).get(field, False)
            gate._add(
                "faults", f"{sub}.{field}", True, ok, "ok" if ok else "FAIL"
            )
        wt_b = bs.get("serve", {}).get("wall_time_s")
        wt_f = fs.get("serve", {}).get("wall_time_s")
        if wt_b is not None and wt_f is not None:
            gate.time("faults", "serve.wall_time_s", wt_b, wt_f)

    # --- challenge: conformance exact, official rate gated tolerantly -
    pair = _section_pair(gate, "challenge", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for field in CHALLENGE_EXACT:
            if field not in bs:
                gate.skip("challenge", f"{field} absent from baseline")
                continue
            if field not in fs:
                gate.missing("challenge", field)
                continue
            gate.exact("challenge", field, bs[field], fs[field])
        # headline invariant, gated regardless of baseline drift: the
        # streamed engine answer set must match the numpy ground truth
        match = fs.get("reference_match", False)
        gate._add(
            "challenge",
            "reference_match",
            True,
            match,
            "ok" if match else "FAIL",
        )
        wt_b, wt_f = bs.get("wall_time_s"), fs.get("wall_time_s")
        if wt_b is not None and wt_f is not None:
            gate.time("challenge", "wall_time_s", wt_b, wt_f)

    # --- gnn: semiring-kernel routing exact, headline wins gated ------
    pair = _section_pair(gate, "gnn", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for field in GNN_EXACT:
            if field not in bs:
                gate.skip("gnn", f"{field} absent from baseline")
                continue
            if field not in fs:
                gate.missing("gnn", field)
                continue
            gate.exact("gnn", field, bs[field], fs[field])
        # headline invariants, gated regardless of baseline drift: the
        # kernel route must launch (and the oracle route must not), its
        # bill must STRICTLY beat the occupancy-equivalent XLA sparse
        # path, and the min_plus Bellman-Ford relaxation must reach the
        # numpy reference fixpoint bit-for-bit.
        launched = (
            fs.get("pallas_calls_conv", 0) >= 1
            and fs.get("pallas_calls_oracle", 1) == 0
        )
        gate._add(
            "gnn",
            "mxm_launches_kernel_route",
            True,
            launched,
            "ok" if launched else "FAIL",
        )
        beat = (
            fs.get("kernel_grid_steps", 1 << 62)
            < fs.get("xla_sparse_grid_steps", 0)
        )
        gate._add(
            "gnn",
            "kernel_beats_xla_sparse_steps",
            True,
            beat,
            "ok" if beat else "FAIL",
        )
        bf_ok = (
            fs.get("bf_converged", False)
            and fs.get("bf_matches_numpy", False)
        )
        gate._add(
            "gnn",
            "bellman_ford_matches_numpy",
            True,
            bf_ok,
            "ok" if bf_ok else "FAIL",
        )
        wt_b, wt_f = bs.get("wall_time_s"), fs.get("wall_time_s")
        if wt_b is not None and wt_f is not None:
            gate.time("gnn", "wall_time_s", wt_b, wt_f)

    # --- tune: sweep accounting exact, headline wins gated ------------
    pair = _section_pair(gate, "tune", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        bsub, fsub = bs.get("skewed", {}), fs.get("skewed", {})
        for field in TUNE_SKEWED_EXACT:
            if field not in bsub:
                gate.skip("tune", f"skewed.{field} absent from baseline")
                continue
            if field not in fsub:
                gate.missing("tune", f"skewed.{field}")
                continue
            gate.exact("tune", f"skewed.{field}", bsub[field], fsub[field])
        # headline invariant, gated regardless of baseline drift: the
        # tuned config must STRICTLY beat the default's grid-step bill
        # on the skewed stack.
        sk = fsub
        won = (
            sk.get("grid_steps_tuned", 1 << 62)
            < sk.get("grid_steps_default", 0)
        )
        gate._add(
            "tune",
            "skewed.tuned_beats_default_steps",
            True,
            won,
            "ok" if won else "FAIL",
        )
        for field in ("wall_s_tuned", "wall_s_default"):
            wt_b, wt_f = bsub.get(field), fsub.get(field)
            if wt_b is not None and wt_f is not None:
                gate.time("tune", f"skewed.{field}", wt_b, wt_f)

    # --- fleet: replicated-serving curves exact, headlines gated ------
    pair = _section_pair(gate, "fleet", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for arm in ("single", "fleet"):
            b_pts = bs.get("curves", {}).get(arm, [])
            f_pts = fs.get("curves", {}).get(arm, [])
            if len(b_pts) != len(f_pts):
                gate.missing(f"fleet.{arm}", "curve points")
                continue
            for bp, fp in zip(b_pts, f_pts):
                name = f"fleet.{arm}@x{bp.get('rate_factor', '?')}"
                for field in FLEET_POINT_EXACT:
                    if field not in bp:
                        gate.skip(name, f"{field} absent from baseline")
                        continue
                    if field not in fp:
                        gate.missing(name, field)
                        continue
                    gate.exact(name, field, bp[field], fp[field])
        for field in ("sustained_jobs_per_s", "fleet_plan_hit_rate_min"):
            if field not in bs:
                gate.skip("fleet", f"{field} absent from baseline")
            elif field not in fs:
                gate.missing("fleet", field)
            else:
                gate.exact("fleet", field, bs[field], fs[field])
        # headline invariants, gated regardless of baseline drift: the
        # replicated fleet must sustain strictly more offered load than
        # one engine at the same miss budget, and the affinity router
        # must hold the fleet-wide plan-cache hit rate at >= 0.9
        sus = fs.get("sustained_jobs_per_s", {})
        single_s, fleet_s = sus.get("single"), sus.get("fleet")
        if single_s is None or fleet_s is None:
            gate.missing("fleet", "sustained_jobs_per_s")
        else:
            gate._add(
                "fleet",
                "sustained: fleet > single",
                single_s,
                fleet_s,
                "ok" if fleet_s > single_s else "FAIL",
            )
        hit = fs.get("fleet_plan_hit_rate_min")
        if hit is None:
            gate.missing("fleet", "fleet_plan_hit_rate_min")
        else:
            gate._add(
                "fleet",
                "plan_hit_rate_min >= 0.9",
                0.9,
                hit,
                "ok" if hit >= 0.9 else "FAIL",
            )
        wt_b, wt_f = bs.get("wall_time_s"), fs.get("wall_time_s")
        if wt_b is not None and wt_f is not None:
            gate.time("fleet", "wall_time_s", wt_b, wt_f)

    # --- serve: deterministic accounting exact, pad waste gated -------
    pair = _section_pair(gate, "serve", baseline, fresh)
    if pair is not None:
        bs, fs = pair
        for arm in ("static", "continuous"):
            for field in SERVE_EXACT:
                gate.exact(
                    f"serve.{arm}", field, bs[arm][field], fs[arm][field]
                )
        # the headline guarantee: pad waste must not regress vs the
        # baseline, and continuous must still beat static outright
        gate.no_worse(
            "serve",
            "continuous.pad_slot_fraction",
            bs["continuous"]["pad_slot_fraction"],
            fs["continuous"]["pad_slot_fraction"],
        )
        strict = (
            fs["continuous"]["pad_slot_fraction"]
            < fs["static"]["pad_slot_fraction"]
        )
        gate._add(
            "serve",
            "continuous < static pad fraction",
            fs["static"]["pad_slot_fraction"],
            fs["continuous"]["pad_slot_fraction"],
            "ok" if strict else "FAIL",
        )
        for arm in ("static", "continuous"):
            gate.time(
                "serve",
                f"wall_time_s.{arm}",
                bs["wall_time_s"][arm],
                fs["wall_time_s"][arm],
            )
    return gate


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("fresh", nargs="?", default=str(DEFAULT_FRESH))
    ap.add_argument("baseline", nargs="?", default=str(DEFAULT_BASELINE))
    ap.add_argument(
        "--tol",
        type=float,
        default=25.0,
        help="wall-clock regression factor tolerated (runner noise)",
    )
    args = ap.parse_args()

    try:
        fresh = json.loads(pathlib.Path(args.fresh).read_text())
    except FileNotFoundError:
        print(
            f"error: fresh artifact {args.fresh} not found — run "
            "`PYTHONPATH=src python -m benchmarks.kernel_bench --quick` first",
            file=sys.stderr,
        )
        return 2
    baseline = json.loads(pathlib.Path(args.baseline).read_text())

    gate = check(baseline, fresh, args.tol)
    print(gate.table())
    n_checks = sum(1 for r in gate.rows if r[4] != "SKIP")
    if gate.failed:
        print(
            f"\nbench gate: {gate.failed}/{n_checks} checks FAILED against "
            f"{args.baseline}",
            file=sys.stderr,
        )
        print(
            "If the change is intentional (new kernel schedule, new "
            "trace), regenerate the baseline:\n"
            "  PYTHONPATH=src python -m benchmarks.kernel_bench --quick\n"
            f"  cp {DEFAULT_FRESH.name} {args.baseline}",
            file=sys.stderr,
        )
        return 1
    print(f"\nbench gate: all {n_checks} checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
