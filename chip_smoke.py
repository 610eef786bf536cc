"""Chip smoke test: the GraphChallenge sparse-DNN engine on a TPU.

Serves the official RadiX-net 16384-neuron × 120-layer challenge stack
(bias -0.4, float32) through the normal entry point,
``repro.serve.run_challenge``, with the engine on its automatic route,
and checks the challenge answer set against the numpy/scipy reference
on the same seeded inputs.

    python chip_smoke.py              # one chip: the single-device engine
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded engine

Every check must pass; any failure exits non-zero. A host whose JAX
finds no TPU fails at the first check. The last line of standard output
is one JSON object naming the device JAX reports. The timings printed
are smoke timings of one short run, not benchmark results.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when that is set,
and ``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
NEURONS, LAYERS = 16384, 120
N_INPUTS, PANEL_WIDTH = 2048, 512
# At 16384 × 120 (bias -0.4, no YMAX clamp) a column's activity either
# dies out or grows without bound; at density 0.4 about half the seeded
# inputs survive, so the answer set is neither empty nor every input.
DENSITY, SEED = 0.4, 0


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"check ok: {what}", flush=True)


def configure_compile_cache() -> None:
    """JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; only when it is
    unset does the cache go to a fixed path in the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache")
        )


def smoke(chips: int) -> dict:
    import jax
    import numpy as np

    from repro.data import radixnet as rx
    from repro.kernels import ops as kernel_ops
    from repro.launch.mesh import make_row_blocks_mesh
    from repro.serve import run_challenge

    devices = jax.devices()
    platform = devices[0].platform
    check(platform == "tpu", f"JAX runs on a TPU (found {platform!r})")
    check(
        len(devices) >= chips,
        f"{chips} chip(s) wanted, {len(devices)} found",
    )
    print(
        f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}",
        flush=True,
    )
    check(
        not kernel_ops.auto_interpret(),
        "Pallas kernels compile for the device (interpret mode is off)",
    )

    spec = rx.RadixNetSpec(NEURONS, LAYERS)
    mesh = make_row_blocks_mesh(chips) if chips > 1 else None
    print(
        f"serving RadiX-net {spec.neurons}x{spec.layers} (bias {spec.bias}, "
        f"{spec.edges} edges): {N_INPUTS} inputs in panels of "
        f"{PANEL_WIDTH}" + (f" on a {chips}-shard mesh" if mesh else ""),
        flush=True,
    )
    t0 = time.perf_counter()
    res = run_challenge(
        spec,
        n_inputs=N_INPUTS,
        panel_width=PANEL_WIDTH,
        density=DENSITY,
        seed=SEED,
        mesh=mesh,
    )
    total = time.perf_counter() - t0
    engine = res.engine

    for i, p in enumerate(res.panels):
        shards = f" shards {p['shards']}" if "shards" in p else ""
        print(
            f"panel {i}: route {p['route']} level {p['level']} "
            f"degraded {p['degraded']}{shards}",
            flush=True,
        )
    check(res.served == N_INPUTS, f"served {res.served} of {N_INPUTS} inputs")
    check(
        not engine.ladder.events,
        f"no degradation-ladder events ({engine.ladder.events})",
    )
    check(not engine.ladder.degraded, "ladder not degraded")
    check(
        not any(p["degraded"] for p in res.panels),
        "no panel served at a degraded level",
    )
    if mesh is not None:
        check(
            all(
                p["route"] == "sharded" and p.get("shards") == chips
                for p in res.panels
            ),
            f"every panel took the sharded route on {chips} shards",
        )

    (plan,) = [p for p in engine.plan_cache.plans() if p.width == PANEL_WIDTH]
    desc = plan.describe()
    paths = sorted(set(desc.get("paths", desc.get("layouts", []))))
    print(
        f"served plan: route {plan.route}, {plan.pallas_calls} kernel "
        f"launches per panel ({', '.join(paths)}), {plan.grid_steps} grid "
        f"steps per panel, {plan.compile_count} compile(s)",
        flush=True,
    )
    t1 = time.perf_counter()
    text = plan.lower().compile().as_text()
    aot = time.perf_counter() - t1
    check("tpu_custom_call" in text, "compiled plan contains tpu_custom_call")
    if mesh is not None:
        check("all-reduce" in text, "compiled sharded plan contains all-reduce")

    print(
        f"smoke timings (one short run, not a benchmark): warmup panel "
        f"(plan build + compile + one panel) {res.warmup_seconds:.3f} s; "
        f"{res.steps} served panels {res.seconds:.3f} s; run_challenge "
        f"total {total:.3f} s; AOT re-compile of the served plan {aot:.3f} s",
        flush=True,
    )

    t2 = time.perf_counter()
    y0 = rx.radixnet_input_panel(
        spec.neurons, N_INPUTS, density=DENSITY, seed=SEED
    )
    ref_y, ref_cats = rx.radixnet_reference(spec, y0)
    print(
        f"reference: {len(ref_cats)} categories in "
        f"{time.perf_counter() - t2:.3f} s",
        flush=True,
    )
    check(bool(np.isfinite(ref_y).all()), "reference activations finite")
    check(
        0 < len(ref_cats) < N_INPUTS,
        f"reference answer set nondegenerate (0 < {len(ref_cats)} < "
        f"{N_INPUTS})",
    )
    check(
        np.array_equal(res.categories, ref_cats),
        f"engine categories ({len(res.categories)}) identical to the "
        f"reference ({len(ref_cats)})",
    )
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="1: the single-device engine; 4: the mesh-sharded engine",
    )
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(
            f"chip_smoke: no src/repro beside {__file__}; run it from a "
            "checkout of the repository",
            file=sys.stderr,
        )
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    configure_compile_cache()
    try:
        device = smoke(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
