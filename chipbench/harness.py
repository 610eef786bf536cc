"""The benchmark harness: finds a cell by name and runs it to the contract.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``chipbench/configs/<config>.json`` -- the configuration's sizes (the
  ``file`` of its ``configs`` entry), with the limits of the comparison
  that decides ``correct``;
* ``chipbench/traffic/<traffic>.json`` -- a traffic mix's parameters,
  read by the one generator and driver in ``chipbench/drivers.py``;
* ``chipbench/metrics/<metric>.py`` -- a per-layer metric's reader, a
  ``read(view)`` that returns a number or None from a traced window.

A run loads, warms up every shape its traffic uses, measures for
``--seconds``, reads the device's peak memory, checks a sample of what
the timed path produced against the benchmark's own reference, and
prints one JSON line last on standard output.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# The persistent compile cache sits at one fixed path inside the checkout:
# the path is part of the cache key, and the benchmark writes nowhere else.
CACHE_DIR = os.path.join(ROOT, ".chipbench_cache")


class NoResult(Exception):
    """The run cannot produce a result on this machine."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # the BENCHMARK.json entries this cell reports
    per_layer: tuple


def _read_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise NoResult(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    return cell_of(w["config"], w["traffic"], chips=int(w["chips"]), name=name, root=root)


def cell_of(
    config: str, traffic: str, *, chips: int = 1, name: str | None = None, root: str = ROOT
) -> Cell:
    """A configuration of ``BENCHMARK.json`` under a traffic mix, named
    ``name`` (the metrics of a cell of that name apply)."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    name = name or f"{config}.{traffic}"
    (entry,) = [c for c in bench["configs"] if c["name"] == config]
    mix = _read_json(os.path.join(root, "chipbench", "traffic", f"{traffic}.json"))
    mix["name"] = traffic
    return Cell(
        name=name,
        chips=chips,
        config=_read_json(os.path.join(root, entry["file"])),
        traffic=mix,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
    )


def load_reader(metric: str, root: str = ROOT):
    """The module ``chipbench/metrics/<metric>.py``."""
    path = os.path.join(root, "chipbench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# device and compile bookkeeping


def configure_jax() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # Small programs too: later runs must find every program in the cache.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def check_device(chips: int) -> dict:
    """The device JAX reports; NoResult unless it is a TPU with at least
    ``chips`` chips whose Pallas kernels compile for it."""
    import jax

    from chipbench.yardstick import peaks as _peaks

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoResult(f"JAX finds no TPU (platform {platform!r})")
    if len(devices) < chips:
        raise NoResult(f"{chips} chips wanted, {len(devices)} found")
    try:
        from repro.kernels import ops as kernel_ops
    except ImportError as e:
        raise NoResult(f"the program is not beside the benchmark: {e}") from e
    if kernel_ops.auto_interpret():
        raise NoResult("the program's Pallas kernels run in interpret mode")
    _peaks.peaks(devices[0].device_kind)  # an unknown chip is an error
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


class CompileCounter:
    """Counts executables that enter the process: compiled, or loaded from
    the persistent cache."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiled = 0
        self.loaded = 0

        def on_duration(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiled += 1

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.loaded += 1

        self._listeners = (on_duration, on_event)
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.compiled, self.loaded

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._listeners[0])
        mon.unregister_event_listener(self._listeners[1])


def peak_memory_bytes(chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


# ---------------------------------------------------------------------------
# a run


def run_cell(
    cell: Cell,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t0: float,
    device: dict,
    wrap_engine=None,
    keep_trace: str | None = None,
    out=sys.stdout,
) -> dict:
    """Set up, measure, check; the result object of the contract.

    ``wrap_engine``, when given, receives the program's engine and returns
    the one the window drives (the tests break the timed path with it).
    ``keep_trace`` names a directory where the profiler's trace is left.
    """
    from chipbench import drivers
    from chipbench.yardstick import peaks as _peaks
    from chipbench.yardstick import trace as _trace

    def say(line: str) -> None:
        print(line, file=out, flush=True)

    ready_s = time.perf_counter() - t0  # imports and the device's start
    counter = CompileCounter()
    driver = drivers.for_traffic(cell.traffic)
    state = driver.setup(cell, seed=seed, seconds=seconds, wrap_engine=wrap_engine)
    setup_s = time.perf_counter() - t0
    say(f"setup {setup_s:.3f} s ({ready_s:.3f} s to the device): {state.describe()}")

    trace_dir = None
    if trace:
        trace_dir = keep_trace or tempfile.mkdtemp(prefix="chipbench-trace-")
    if trace:
        import jax

        jax.profiler.start_trace(trace_dir)
    before = counter.snapshot()
    win = driver.window(state, seconds=seconds)
    compiled, loaded = (a - b for a, b in zip(counter.snapshot(), before))
    counter.close()
    view = None
    if trace:
        jax.profiler.stop_trace()
    memory_peak = peak_memory_bytes(cell.chips)
    say(
        f"window {win.seconds:.3f} s: {win.describe()}; executables entering "
        f"the process inside the window: {compiled} compiled, {loaded} "
        "loaded from the cache"
    )
    if trace:
        try:
            view = _trace.load_window(
                trace_dir,
                chips=cell.chips,
                cell=cell,
                peaks=_peaks.peaks(device["kind"]),
                counters=win.counters,
            )
        finally:
            if not keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)

    checks = driver.check(state, win, seed=seed, say=say)
    del state
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = dict(device, memory_peak_bytes=memory_peak)
    metrics = {}
    result = {
        "correct": correct,
        "attempted": win.attempted,
        "failed": win.failed,
        "metrics": metrics,
        "device": device,
    }
    if not trace:
        values = dict(win.end_to_end, setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] not in values:
                raise RuntimeError(f"{cell.name} does not measure {m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            got = load_reader(m["name"]).read(view)
            if got is None:
                continue
            value, note = got if isinstance(got, tuple) else (got, None)
            if note:
                say(f"{m['name']}: {note}")
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=view.busy_s, window_s=view.window_s)
        result["breakdown"] = view.breakdown()
    result["checks"] = checks  # last: each number compared, with its limit
    return result


def main(argv, *, t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one cell of the chip benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR", help="leave the trace in DIR")
    args = ap.parse_args(argv)
    # libtpu logs to a fixed path under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        cell = load_cell(args.workload)
        import jax  # noqa: F401

        configure_jax()
        device = check_device(cell.chips)
        result = run_cell(
            cell,
            seed=args.seed,
            seconds=args.seconds,
            trace=bool(args.trace),
            t0=t0,
            device=device,
            keep_trace=args.keep_trace,
        )
    except NoResult as e:
        print(f"chipbench: no result: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
