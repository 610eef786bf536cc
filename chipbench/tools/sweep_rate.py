"""Find the highest request rate an open-loop cell sustains, in one process.

    python3 chipbench/tools/sweep_rate.py --config radixnet-1024x120 \
        --traffic open-poisson-d0.3 --rates 250,500,1000,2000 --seconds 10

Builds and warms the cell once, then drives one window per rate through
the same open loop the benchmark times and prints, per rate, the latency
percentiles, the backlog in the window's first and last thirds and how
long the queue took to drain after the last arrival. A rate is sustained
when the backlog does not grow across the window: the last third's mean
backlog is no larger than the first third's plus one panel's worth of
arrivals, and the queue drains within two step times. The last line
gives the highest sustained rate and 4/5 of it, the cell's rate
(PERF.md has the sweep).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2**31 + 101)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from chipbench import drivers, harness

    harness.configure_jax()
    cell = harness.cell_of(args.config, args.traffic)
    device = harness.check_device(cell.chips)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.traffic} is not an open-loop mix")
    say = lambda line: print(line, flush=True)  # noqa: E731
    t = time.perf_counter()
    state = drivers.Open.setup(cell, seed=args.seed, seconds=args.seconds)
    say(f"setup {time.perf_counter() - t:.3f} s on {device}: {state.describe()}")
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        fresh = drivers.Open.prepare(
            cell, state.engine, seed=args.seed, seconds=args.seconds, rate=rate
        )
        win = drivers.Open.window(fresh, seconds=args.seconds)
        c = win.counters
        first, last = c["backlog_thirds"]
        sustained = bool(
            win.failed == 0
            and last <= first + rate * c["step_s"]
            and c["drain_s"] <= 2 * c["step_s"]
        )
        say(json.dumps({"rate_per_s": rate, "sustained": sustained,
                        "requests": win.attempted, "failed": win.failed,
                        **win.end_to_end, "step_s": c["step_s"],
                        "backlog_thirds": [first, last], "drain_s": c["drain_s"],
                        "window": win.describe()}))
        if not sustained:
            break  # past the knee: a higher rate only queues longer
        knee = rate
        del win, fresh
    say(json.dumps({"highest_sustained_per_s": knee,
                    "cell_rate_per_s": None if knee is None else round(0.8 * knee)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
