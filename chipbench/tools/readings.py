"""Readings that the limits of ``correct`` are set from, in one process.

    python3 chipbench/tools/readings.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3 --seconds 3

Builds and warms the cell once, then for each seed makes that seed's
inputs and drives a short window through the timed path, compares the
seeded sample of its answers with the reference, and prints every number
the comparison knows (the lower readings). For each control seed it also
puts the control in the program's place on the same sampled inputs: the
reference with its activations rounded as the three-pass (``high``)
matrix product rounds them, the nearest precision below the float32 at
``highest`` that the configuration states. What the comparison reads
for it are the upper readings. One JSON line per reading.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if sys.path and os.path.abspath(sys.path[0] or ".") == HERE:
    sys.path.pop(0)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def seeds(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seed-offset", type=int, default=2**31)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from chipbench import drivers, harness
    from chipbench.yardstick.check import numbers

    harness.configure_jax()
    cell = harness.load_cell(args.workload)
    device = harness.check_device(cell.chips)
    driver = drivers.for_traffic(cell.traffic)
    say = lambda line: print(line, flush=True)  # noqa: E731
    t = time.perf_counter()
    state = driver.setup(cell, seed=args.seed_offset, seconds=args.seconds)
    say(f"setup {time.perf_counter() - t:.3f} s on {device}: {state.describe()}")
    for s in sorted(set(args.seeds) | set(args.control_seeds)):
        seed = args.seed_offset + s
        fresh = driver.prepare(cell, state.engine, seed=seed, seconds=args.seconds)
        win = driver.window(fresh, seconds=args.seconds)
        y, mask, y0 = driver.sampled(fresh, win, seed=seed)
        t = time.perf_counter()
        ref = drivers._reference(cell.config, y0)
        ref_s = time.perf_counter() - t
        if s in args.seeds:
            got = numbers(y, mask, ref, lost=win.failed)
            say(json.dumps({"seed": seed, "side": "program", "inputs": y0.shape[1],
                            "numbers": got, "reference_s": ref_s,
                            "window": win.describe()}))
        if s in args.control_seeds:
            ctl = drivers._reference(cell.config, y0, operands="high")
            got = numbers(ctl, ctl.max(axis=0) > 0, ref, lost=0)
            say(json.dumps({"seed": seed, "side": "control-high",
                            "inputs": y0.shape[1], "numbers": got}))
        del win, fresh
    return 0


if __name__ == "__main__":
    sys.exit(main())
