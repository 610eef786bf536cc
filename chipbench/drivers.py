"""The one traffic generator and its two drivers.

A traffic file names its loop:

* ``"closed"`` -- one stream of ``panel_width``-input panels through
  ``SparseDNNEngine.submit``/``step``; each panel goes in after the
  previous panel's activity mask (the challenge answer) is on the host,
  as the program's ``run_challenge`` does. Reports
  ``edge_inputs_per_s``: 32 * neurons * layers * inputs completed over
  the window, which ends with the last whole panel.
* ``"open"`` -- Poisson arrivals at ``rate_per_s``, one input per
  request, submitted as host arrays to a ``ContinuousBatcher`` over the
  engine. Reports ``latency_p50_ms`` and ``latency_p95_ms`` over every
  request scheduled in the window, each from its scheduled arrival to
  the return of the step that served it.

Inputs are seeded {0, 1} columns at the mix's ``density``, made by the
benchmark's own generator. After the window a sample of the answers,
drawn from the seed, is compared with the benchmark's own reference.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Callable

import numpy as np

from chipbench.yardstick import arrivals as _arrivals
from chipbench.yardstick import radixnet as _yr
from chipbench.yardstick.check import UNREADABLE, compare_columns


def span(name: str):
    """A benchmark span on the profiler's clock (free when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(f"chipbench.{name}")


def build_engine(config: dict, traffic: dict):
    """The program's engine over the configuration's stack."""
    from repro.data import radixnet as prx
    from repro.serve import SparseDNNEngine

    if config["fan_in"] != _yr.FAN_IN or config["weight"] != _yr.WEIGHT_VALUE:
        raise ValueError("RadiX-net stacks have fan-in 32 and weight 1/16")
    if config["ymax"] is not None:
        raise ValueError("the program serves plain ReLU, with no YMAX clamp")
    spec = prx.RadixNetSpec(config["neurons"], config["layers"], bias=config["bias"])
    weights, biases = prx.radixnet_weights(spec, block_size=config["block_size"])
    return SparseDNNEngine(weights, biases, batch_align=traffic["batch_align"])


def describe_plan(engine, width: int) -> str:
    plans = [p for p in engine.plan_cache.plans() if p.width == width]
    if not plans:
        return "no plan"
    p = plans[0]
    return (
        f"route {p.route}, {p.pallas_calls} kernel launches and "
        f"{p.grid_steps} grid steps per {width}-wide panel"
    )


@dataclasses.dataclass
class Window:
    seconds: float
    attempted: int
    failed: int
    end_to_end: dict
    counters: dict  # what the per-layer readers need
    answers: Any = None  # what the check reads, freed after it
    note: str = ""

    def describe(self) -> str:
        return f"{self.attempted} attempted, {self.failed} failed; {self.note}"


def _reference(config: dict, y0: np.ndarray, *, operands: str = "float32"):
    return _yr.stack_reference(
        config["neurons"],
        config["layers"],
        config["bias"],
        y0,
        chunk=64,
        operands=operands,
    )


def _percentile(values: np.ndarray, q: float) -> float:
    """A percentile of the answers that came; none came reads as endless."""
    return float(np.percentile(values, q)) if len(values) else UNREADABLE


def _sample(n_done: int, k: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 7])
    return np.sort(rng.choice(n_done, size=min(k, n_done), replace=False))


# ---------------------------------------------------------------------------
# closed loop: the challenge stream


@dataclasses.dataclass
class ClosedState:
    cell: Any
    engine: Any
    pool: np.ndarray  # (neurons, pool_inputs) host inputs
    panels: list  # the pool as device panels
    active: Callable  # panel -> per-input activity mask
    phases: dict = dataclasses.field(default_factory=dict)  # set-up seconds by phase

    def describe(self) -> str:
        w = self.cell.traffic["panel_width"]
        phases = ", ".join(f"{k} {v:.3f} s" for k, v in self.phases.items())
        return (
            f"{self.cell.config['name']}: {describe_plan(self.engine, w)}; "
            f"{len(self.panels)} distinct panels of {w} inputs; {phases}"
        )


class Closed:
    @staticmethod
    def prepare(cell, engine, *, seed: int, seconds: float) -> ClosedState:
        import jax

        t = cell.traffic
        w = t["panel_width"]
        pool = _yr.radixnet_input_panel(
            cell.config["neurons"], t["pool_inputs"], density=t["density"], seed=seed
        )
        panels = [jax.device_put(pool[:, s : s + w]) for s in range(0, pool.shape[1], w)]
        jax.block_until_ready(panels)
        active = jax.jit(lambda y: (y > 0).any(axis=0))
        return ClosedState(cell, engine, pool, panels, active)

    @staticmethod
    def warm(state: ClosedState) -> None:
        """One panel through the window's own calls, which loads or
        compiles every program a panel runs (a second panel found
        nothing more to load: 0 executables entered any window)."""
        w = state.cell.traffic["panel_width"]
        state.engine.submit(state.panels[0])
        out, _ = state.engine.step(pad_to=w)
        if out is not None:
            np.asarray(state.active(out))

    @classmethod
    def setup(cls, cell, *, seed, seconds, wrap_engine=None):
        t = [time.perf_counter()]
        engine = build_engine(cell.config, cell.traffic)
        if wrap_engine is not None:
            engine = wrap_engine(engine)
        t.append(time.perf_counter())
        state = cls.prepare(cell, engine, seed=seed, seconds=seconds)
        t.append(time.perf_counter())
        cls.warm(state)
        t.append(time.perf_counter())
        state.phases = dict(zip(("engine", "inputs", "warm-up"), np.diff(t)))
        return state

    @staticmethod
    def window(state: ClosedState, *, seconds: float) -> Window:
        cfg, t = state.cell.config, state.cell.traffic
        w = t["panel_width"]
        n_pool = len(state.panels)
        outs, masks, panel_s = [], [], []
        failed = 0
        t0 = time.perf_counter()
        with span("window"):
            while True:
                p = len(outs)
                ts = time.perf_counter()
                with span("panel"):
                    state.engine.submit(state.panels[p % n_pool])
                    with span("engine.step"):
                        out, stats = state.engine.step(pad_to=w)
                    if out is None or stats["failed"]:
                        failed += w
                        out = mask = None
                    else:
                        with span("mask_readback"):
                            mask = np.asarray(state.active(out))
                outs.append(out)
                masks.append(mask)
                now = time.perf_counter()
                panel_s.append(now - ts)
                if now - t0 >= seconds:
                    break
        elapsed = now - t0
        done = sum(w for o in outs if o is not None)
        rate = _yr.FAN_IN * cfg["neurons"] * cfg["layers"] * done / elapsed
        ps = sorted(panel_s)
        return Window(
            seconds=elapsed,
            attempted=len(outs) * w,
            failed=failed,
            end_to_end={"edge_inputs_per_s": rate},
            counters={"inputs": done, "panels": len(outs), "panel_width": w},
            answers=(outs, masks),
            note=(
                f"{len(outs)} panels; panel seconds min {ps[0]:.6f} median "
                f"{ps[len(ps) // 2]:.6f} max {ps[-1]:.6f}"
            ),
        )

    @staticmethod
    def sampled(state: ClosedState, win: Window, *, seed: int):
        """(program outputs, program masks, inputs) of the seeded sample."""
        outs, masks = win.answers
        w = state.cell.traffic["panel_width"]
        n_pool = len(state.panels)
        done = [p for p, o in enumerate(outs) if o is not None]
        pick = _sample(len(done) * w, state.cell.traffic["sample_inputs"], seed)
        by_panel: dict[int, list[int]] = {}
        for g in pick:
            by_panel.setdefault(done[g // w], []).append(int(g % w))
        neurons = state.cell.config["neurons"]
        ys, ms, cols = [np.zeros((neurons, 0), np.float32)], [np.zeros(0, bool)], []
        for p, js in by_panel.items():
            ys.append(np.asarray(outs[p])[:, js])
            ms.append(masks[p][js])
            cols.extend((p % n_pool) * w + j for j in js)
        return np.concatenate(ys, axis=1), np.concatenate(ms), state.pool[:, cols]

    @classmethod
    def check(cls, state: ClosedState, win: Window, *, seed: int, say) -> dict:
        y, mask, y0 = cls.sampled(state, win, seed=seed)
        win.answers = None
        t = time.perf_counter()
        ref = _reference(state.cell.config, y0)
        say(f"reference over {y0.shape[1]} sampled inputs: {time.perf_counter() - t:.3f} s")
        return compare_columns(y, mask, ref, state.cell.config["limits"], lost=win.failed)


# ---------------------------------------------------------------------------
# open loop: served requests


@dataclasses.dataclass
class OpenState:
    cell: Any
    engine: Any
    rate: float
    arrivals: np.ndarray  # scheduled seconds from the window's start
    requests: np.ndarray  # (n, neurons) host inputs, one row per request

    def describe(self) -> str:
        t = self.cell.traffic
        return (
            f"{self.cell.config['name']}: {describe_plan(self.engine, t['batch_size'])}; "
            f"{len(self.arrivals)} requests at {self.rate} /s"
        )


def _batcher(engine, traffic: dict):
    from repro.plan import DEFAULT_WIDTH_CLASSES
    from repro.serve.scheduler import ContinuousBatcher

    return ContinuousBatcher(
        engine,
        batch_size=traffic["batch_size"],
        min_fill=traffic["min_fill"],
        width_classes=DEFAULT_WIDTH_CLASSES,
    )


class Open:
    @staticmethod
    def prepare(cell, engine, *, seed, seconds, rate=None) -> OpenState:
        t = cell.traffic
        rate = t["rate_per_s"] if rate is None else rate
        if rate is None:
            raise ValueError(
                f"traffic {t['name']!r} states no rate: find it with "
                "chipbench/tools/sweep_rate.py"
            )
        times = _arrivals.permuted_arrivals(rate, seconds, seed)
        times = times[times < seconds]
        inputs = _yr.radixnet_input_panel(
            cell.config["neurons"], len(times), density=t["density"], seed=seed
        )
        return OpenState(cell, engine, rate, times, np.ascontiguousarray(inputs.T))

    @staticmethod
    def warm(state: OpenState) -> None:
        """One step at every batch the batcher can take, 1 to batch_size.

        Each new count stacks, pads, checks and slices at new shapes of
        (neurons, count), which do not depend on the stack's depth, and
        each width class runs the stack's own program. So every count
        runs once on a one-layer stack of the same width, and each width
        class once on the cell's own stack, instead of the deep stack
        once per count."""
        import jax
        from repro.plan import DEFAULT_WIDTH_CLASSES, quantize_width

        t = state.cell.traffic
        counts = range(1, t["batch_size"] + 1)
        classes = sorted({quantize_width(k, DEFAULT_WIDTH_CLASSES) for k in counts})
        shallow = build_engine(dict(state.cell.config, layers=1), t)
        n = len(state.requests)
        for engine, sizes in ((state.engine, classes), (shallow, counts)):
            batcher, rec = _batcher(engine, t), None
            for k in sizes:
                for r in range(k):
                    batcher.submit(state.requests[r % n])
                rec = batcher.step() or rec
            if rec is not None:
                jax.block_until_ready(batcher.result(rec.request_ids[-1]))

    @classmethod
    def setup(cls, cell, *, seed, seconds, wrap_engine=None):
        engine = build_engine(cell.config, cell.traffic)
        if wrap_engine is not None:
            engine = wrap_engine(engine)
        step = engine.step

        def traced_step(*args, **kwargs):  # the batcher's call into the engine
            with span("engine.step"):
                return step(*args, **kwargs)

        engine.step = traced_step
        state = cls.prepare(cell, engine, seed=seed, seconds=seconds)
        cls.warm(state)
        return state

    @staticmethod
    def window(state: OpenState, *, seconds: float) -> Window:
        arrivals = state.arrivals
        n = len(arrivals)
        batcher = _batcher(state.engine, state.cell.traffic)
        done_at = np.full(n, math.nan)
        step_start = np.full(n, math.nan)
        lag = np.zeros(n)
        backlog = []  # (seconds, requests waiting) before each step
        sizes = []
        i = 0
        t0 = time.perf_counter()
        with span("window"):
            while True:
                now = time.perf_counter() - t0
                if i < n and arrivals[i] <= now:
                    with span("submit"):
                        while i < n and arrivals[i] <= now:
                            batcher.submit(state.requests[i])
                            lag[i] = now - arrivals[i]
                            i += 1
                if len(batcher.queue):
                    ts = time.perf_counter() - t0
                    backlog.append((ts, len(batcher.queue)))
                    with span("batcher.step"):
                        rec = batcher.step()
                    te = time.perf_counter() - t0
                    if rec is not None:
                        ids = np.asarray(rec.request_ids)
                        done_at[ids] = te
                        step_start[ids] = ts
                        sizes.append(len(ids))
                elif i < n:
                    with span("wait_arrival"):
                        time.sleep(max(0.0, arrivals[i] - (time.perf_counter() - t0)))
                else:
                    break
        elapsed = time.perf_counter() - t0
        lost = sorted(batcher.failures)
        done_at[lost] = math.nan
        ok = ~np.isnan(done_at)
        lat_ms = (done_at[ok] - arrivals[ok]) * 1e3
        wait_ms = (step_start[ok] - arrivals[ok]) * 1e3
        third = [b for t, b in backlog if t < seconds / 3], [
            b for t, b in backlog if t >= 2 * seconds / 3 and t < seconds
        ]
        step_s = float(np.median(np.diff([t for t, _ in backlog]))) if len(backlog) > 1 else 0.0
        return Window(
            seconds=elapsed,
            attempted=n,
            failed=int(n - ok.sum()),
            end_to_end={
                "latency_p50_ms": _percentile(lat_ms, 50),
                "latency_p95_ms": _percentile(lat_ms, 95),
            },
            counters={
                "queue_wait_ms": wait_ms,
                "steps": len(sizes),
                "step_s": step_s,
                "backlog_thirds": (float(np.mean(third[0] or [0])), float(np.mean(third[1] or [0]))),
                "drain_s": elapsed - arrivals[-1],
            },
            answers=batcher,
            note=(
                f"{len(sizes)} steps, mean batch {np.mean(sizes or [0]):.1f}, max "
                f"{max(sizes, default=0)}; generator late by p50 {np.median(lag) * 1e3:.3f} "
                f"ms, p99 {np.percentile(lag, 99) * 1e3:.3f} ms, max "
                f"{lag.max() * 1e3:.3f} ms; backlog mean first third "
                f"{np.mean(third[0] or [0]):.1f}, last third "
                f"{np.mean(third[1] or [0]):.1f}, max {max((b for _, b in backlog), default=0)}; "
                f"drained {elapsed - arrivals[-1]:.3f} s after the last arrival"
            ),
        )

    @staticmethod
    def sampled(state: OpenState, win: Window, *, seed: int):
        batcher = win.answers
        done = np.asarray(
            [r for r in range(win.attempted) if r not in batcher.failures], np.int64
        )
        pick = done[_sample(len(done), state.cell.traffic["sample_inputs"], seed)]
        y = np.zeros((state.cell.config["neurons"], len(pick)), np.float32)
        for j, r in enumerate(pick):
            y[:, j] = np.asarray(batcher.result(int(r)))
        return y, (y > 0).any(axis=0), np.ascontiguousarray(state.requests[pick].T)

    @classmethod
    def check(cls, state: OpenState, win: Window, *, seed: int, say) -> dict:
        y, mask, y0 = cls.sampled(state, win, seed=seed)
        win.answers = None
        t = time.perf_counter()
        ref = _reference(state.cell.config, y0)
        say(f"reference over {y0.shape[1]} sampled requests: {time.perf_counter() - t:.3f} s")
        return compare_columns(y, mask, ref, state.cell.config["limits"], lost=win.failed)


def for_traffic(traffic: dict):
    loops = {"closed": Closed, "open": Open}
    if traffic.get("loop") not in loops:
        raise ValueError(f"traffic {traffic.get('name')!r} has no known loop")
    return loops[traffic["loop"]]
