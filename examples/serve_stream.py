"""Streaming-inference demo: continuous batching vs static batching.

Builds the paper's deep sparse ReLU MLP, replays a deterministic bursty
(Poisson-ish) request stream through it twice over the same weights —

  1. **static aligned batching** — the pre-scheduler setup: every tick's
     arrivals are served immediately as one right-padded batch at a
     fixed service width (``SparseDNNEngine.infer``);
  2. **continuous batching** — ``repro.serve.ContinuousBatcher`` packs
     pending requests into tile-aligned panels each scheduling tick
     (late arrivals join mid-stream, completed requests free their
     slots), driving the engine's ``submit``/``step``/``drain`` API —

and prints the head-to-head ServeStats: pad-slot fraction, exact kernel
grid steps per served row, and the latency distribution. The grid-step
columns are hardware-independent: the pad columns of every underfull
static batch ride through all L layers' kernel grids, which is exactly
the work the scheduler removes.

``--tuned`` serves through the committed autotuner table
(``examples/tuning_table.json``, a ``repro.tune.TuningTable``): the
engine looks up this topology's fingerprint and re-plans every width
class with the winning config (here the default one: no forced layout
lowers the bill of these random stacks). On a fingerprint miss the
example sweeps in-process (``repro.tune.tune_stack``) and warns that
the refreshed table should be committed. The tuned plan's grid-step
bill is asserted no worse than the default plan's.

``--shards N`` serves the same trace through a mesh-sharded engine
(``SparseDNNEngine(mesh=...)``): every layer's block-CSR segment is
partitioned across N row-block shards (``repro.sparse.partition``) and
executed under shard_map with a psum between layers — outputs are
identical, and the step stats grow per-shard grid-step bills that sum
to the single-device bill. On CPU hosts the flag fakes N host devices
(it must run before the first jax import, which is why it is parsed
early below).

Run: PYTHONPATH=src python examples/serve_stream.py [--quick] [--tuned]
     [--shards N]
Docs: docs/serving.md (design), docs/architecture.md (Distribution),
docs/benchmarks.md (serve/sharded arm fields).
"""

import argparse
import os
import sys


def _early_shards() -> int:
    """Read --shards before the first jax import: fake host devices
    only materialize if XLA_FLAGS is set at process start."""
    for i, a in enumerate(sys.argv):
        if a == "--shards" and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith("--shards="):
            return int(a.split("=", 1)[1])
    return 1


_SHARDS = _early_shards()
if _SHARDS > 1:
    _flags = os.environ.get("XLA_FLAGS", "")
    # Append to (never clobber) whatever XLA_FLAGS the user already has;
    # an explicit device-count flag from the caller wins.
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " " if _flags else "") + (
            f"--xla_force_host_platform_device_count={_SHARDS}"
        )

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dnn
from repro.launch.mesh import make_row_blocks_mesh
from repro.serve import (
    ContinuousBatcher,
    SparseDNNEngine,
    poissonish_trace,
    serve_trace_static,
)
from repro.sparse.bcsr import BlockCSRMatrix
from repro.sparse.bsr import BlockSparseMatrix


def build_stack(m: int, layers: int, bpr: int):
    ws = [
        BlockSparseMatrix.random(
            jax.random.PRNGKey(i), (m, m), (16, 16), blocks_per_row=bpr
        )
        for i in range(layers)
    ]
    bs = [jnp.zeros((m,), jnp.float32) for _ in range(layers)]
    return ws, bs


def report(tag: str, s) -> None:
    print(
        f"  {tag:11s} steps={s.engine_steps:3d}  rows={s.rows_served:3d}  "
        f"padded_slots={s.padded_slots:4d}  pad_frac={s.pad_slot_fraction:.3f}  "
        f"grid_steps={s.grid_steps_total:5d} "
        f"({s.grid_steps_per_row:.2f}/row)  "
        f"latency p50/mean/max = {s.latency_p50:.0f}/{s.latency_mean:.2f}/"
        f"{s.latency_max}"
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--blocks-per-row", type=int, default=2)
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--tile-align", type=int, default=8)
    ap.add_argument("--lam", type=float, default=3.0)
    ap.add_argument("--min-fill", type=float, default=0.25)
    ap.add_argument("--max-wait", type=int, default=3)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument(
        "--shards",
        type=int,
        default=1,
        help="serve mesh-sharded over N row-block shards (fakes N host "
        "devices on CPU; parsed before the jax import)",
    )
    ap.add_argument(
        "--tuned",
        action="store_true",
        help="serve through the committed autotuner table "
        "(examples/tuning_table.json; sweeps in-process on a miss)",
    )
    ap.add_argument(
        "--quick", action="store_true", help="small shapes for CI (seconds)"
    )
    args = ap.parse_args()
    if args.quick:
        args.m, args.layers, args.requests = 32, 2, 30

    mesh = make_row_blocks_mesh(args.shards) if args.shards > 1 else None
    ws, bs = build_stack(args.m, args.layers, args.blocks_per_row)

    table = None
    if args.tuned:
        from repro import plan as plan_mod
        from repro import tune

        table_path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tuning_table.json"
        )
        table = tune.TuningTable.load(table_path)
        fp = plan_mod.topology_fingerprint(ws)
        if table.lookup(fp) is None:
            print(
                f"[tune] no entry for fingerprint {fp[:12]}… in "
                f"{table_path} — sweeping in-process (commit the "
                "refreshed table to skip this)"
            )
            _, table = tune.tune_stack(
                ws, bs, args.batch_size, table=table, time_forwards=False
            )
        print(f"[tune] serving with config: {table.lookup(fp).token()}")
    trace = poissonish_trace(
        args.requests,
        m=args.m,
        lam=args.lam,
        burst_every=8,
        burst_size=12,
        seed=args.seed,
    )
    counts = [len(a) for a in trace]
    print(
        f"== serving {args.requests} requests over {len(trace)} ticks "
        f"(λ≈{args.lam}, bursts of 12 every 8 ticks) through "
        f"{args.layers}L of {args.m}² sparse MLP =="
    )
    print(f"arrivals/tick: {counts}")

    if mesh is not None:
        print(
            f"mesh-sharded serving: {args.shards} row-block shards over "
            f"{len(jax.devices())} host devices"
        )
    static = serve_trace_static(
        SparseDNNEngine(
            ws,
            bs,
            batch_align=args.batch_size,
            mesh=mesh,
            tuning_table=table,
        ),
        trace,
    )
    engine = SparseDNNEngine(
        ws, bs, batch_align=args.tile_align, mesh=mesh, tuning_table=table
    )
    batcher = ContinuousBatcher(
        engine,
        batch_size=args.batch_size,
        min_fill=args.min_fill,
        max_wait=args.max_wait,
    )
    continuous = batcher.run_trace(trace)

    print("\nhead-to-head (same weights, same trace):")
    report("static", static)
    report("continuous", continuous)
    saved = static.grid_steps_total - continuous.grid_steps_total
    print(
        f"\ncontinuous batching removed {saved} of "
        f"{static.grid_steps_total} kernel grid steps "
        f"({saved / static.grid_steps_total:.1%}) at a latency cost of "
        f"{continuous.latency_mean - static.latency_mean:.2f} ticks mean."
    )

    if mesh is not None:
        # one probe panel to surface the per-shard grid-step accounting;
        # compare against the INDEPENDENTLY computed single-device
        # occupancy-exact bill of the (relayouted) CSR stack — when the
        # shard count divides the stored blocks the two are equal, else
        # the per-shard segment padding shows up as extra steps
        _, pstats = engine.infer(trace[0][0][:, None])
        per = pstats["plan"]["grid_steps_per_shard"]
        total = sum(per)
        csr_ws = [BlockCSRMatrix.from_bsr(w) for w in ws]
        expected = dnn.dnn_grid_steps(csr_ws, pstats["padded_batch"])
        note = (
            f"= the single-device bill {expected}"
            if total == expected
            else f"vs single-device bill {expected}: "
            f"+{total - expected} shard-padding steps"
        )
        print(
            f"\nper-shard grid-step bill for one "
            f"{pstats['padded_batch']}-wide panel: {per} (Σ = {total} "
            f"{note})"
        )
        assert total >= expected and total == pstats["grid_steps"]

    tuned_cfg = engine.tuned if args.tuned else None
    if tuned_cfg is not None:
        # the tuned plan can never bill more kernel grid steps than the
        # default plan for the same width class — the sweep's cost-model
        # scoring only displaces the default on a strict improvement
        from repro import plan as plan_mod

        p_def = plan_mod.build_plan(ws, bs, args.batch_size)
        p_tun = plan_mod.build_plan(ws, bs, args.batch_size, tuned=tuned_cfg)
        assert p_tun.grid_steps <= p_def.grid_steps, (
            p_tun.grid_steps,
            p_def.grid_steps,
        )
        print(
            f"\n[tune] {tuned_cfg.token()}: route {p_def.route}"
            f"→{p_tun.route}, grid steps {p_def.grid_steps}"
            f"→{p_tun.grid_steps} at width {args.batch_size}"
        )

    # spot-check: the batcher's per-request outputs are the real forward
    # (for --shards > 1 this also proves sharded == single-device math).
    ref = dnn.dnn_forward(ws, bs, trace[0][0][:, None], fused=True)[:, 0]
    np.testing.assert_allclose(
        np.asarray(batcher.result(0)), np.asarray(ref), rtol=1e-5, atol=1e-5
    )
    assert continuous.requests == static.requests == args.requests
    assert continuous.pad_slot_fraction < static.pad_slot_fraction
    print("[check] request 0 output matches the reference forward; "
          "pad waste strictly improved")


if __name__ == "__main__":
    main()
