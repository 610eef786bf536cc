"""The harness finds cells, configurations, traffic mixes and per-layer
readers by the names BENCHMARK.json gives, and refuses to measure
without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness
from chipbench.tests._tiny import run, tiny_cell

ROOT = harness.ROOT


def test_every_name_in_the_benchmark_has_its_file():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in [m["name"] for m in cell.end_to_end]
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]).read)


def test_new_files_are_found_by_name(tmp_path):
    """A later change adds a cell with only new files: a configuration, a
    traffic mix and a per-layer reader."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    config = json.load(open(os.path.join(ROOT, bench["configs"][1]["file"])))
    config.update(name="radixnet-4096x120", neurons=4096, bias=-0.35)
    (tmp_path / "chipbench/configs/radixnet-4096x120.json").write_text(json.dumps(config))
    (tmp_path / "chipbench/traffic/stream256-d0.5.json").write_text(
        json.dumps({"loop": "closed", "panel_width": 256, "batch_align": 32,
                    "density": 0.5, "pool_inputs": 1024, "sample_inputs": 64})
    )
    (tmp_path / "chipbench/metrics/panels.challenge.py").write_text(
        "def read(view):\n    return view.counters.get('panels')\n"
    )
    bench["configs"].append({"name": "radixnet-4096x120", "source": "x",
                             "file": "chipbench/configs/radixnet-4096x120.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "challenge-4096x120", "config": "radixnet-4096x120",
                               "traffic": "stream256-d0.5", "chips": 1, "why": "x"})
    (rate,) = [m for m in bench["end_to_end"] if m["name"] == "edge_inputs_per_s"]
    rate["workloads"].append("challenge-4096x120")
    bench["per_layer"].append({"name": "panels.challenge", "unit": "panels",
                               "better": "higher", "source": "program_counter",
                               "layer": "driver", "moves": "edge_inputs_per_s",
                               "workloads": ["challenge-4096x120"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("challenge-4096x120", root=str(tmp_path))
    assert cell.config["neurons"] == 4096
    assert cell.traffic["panel_width"] == 256 and cell.traffic["name"] == "stream256-d0.5"
    assert [m["name"] for m in cell.per_layer] == ["panels.challenge"]
    assert {m["name"] for m in cell.end_to_end} == {"edge_inputs_per_s", "setup_s"}
    reader = harness.load_reader("panels.challenge", root=str(tmp_path))

    class View:
        counters = {"panels": 7}

    assert reader.read(View()) == 7


def test_traced_run_reports_only_what_it_can_read():
    """Off the chip the trace has no device plane: the readers that need
    one return nothing, and the metric is left out of the line."""
    res = run(tiny_cell("challenge-1024x120"), trace=True)
    assert set(res["metrics"]) == {"step_mfu"}
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "challenge-1024x120",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_no_result_without_a_chip():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_no_result_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {"PYTHONPATH": ""}
    proc = _run_py(tmp_path, env)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


@pytest.mark.parametrize("name", ["challenge-1024x120", "nonexistent-cell"])
def test_unknown_or_known_cell_without_chip_exits_nonzero(name):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", name, "--seed", "1",
         "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2
    assert proc.stdout.strip() == "" or '"correct"' not in proc.stdout
