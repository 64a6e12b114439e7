"""A new cell is data and files only, and the harness refuses what it must.

The discovery test copies the benchmark into a temporary checkout, adds a
configuration, a traffic mix, a limit file, a per-layer metric reader and
their BENCHMARK.json entries, and runs the new cell there on the CPU (the
harness's look for a TPU skipped) with no code edited.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from bench_helpers import BENCH, ROOT, SMALL, load_harness
from bench_helpers import harness  # noqa: F401  (fixture)


def _checkout(tmp_path, with_program=True):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    if with_program:
        os.symlink(ROOT / "src", tmp_path / "src")
    return tmp_path / "benchmarks" / "chip"


def test_new_cell_is_found_by_name(tmp_path):
    bench_dir = _checkout(tmp_path)
    config = json.loads((bench_dir / "configs" / "granite-3-2b.json").read_text())
    config["name"] = "toy"
    config["model_config"].update(SMALL, name="toy")
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(config))
    (bench_dir / "traffic" / "burst.json").write_text(json.dumps(
        {"entry": "serve_waves", "batch": 2, "max_len": 48,
         "window_opens": "wave_start", "check_requests": 2,
         "waves": [[9, 5], [20, 12]]}))
    shutil.copy(bench_dir / "limits" / "granite-3-2b.chat.json",
                bench_dir / "limits" / "toy.burst.json")
    (bench_dir / "metrics" / "toy_waves.py").write_text(
        "def read(run, cell):\n    return float(len(run['records']))\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "https://example.org/toy",
                             "file": "benchmarks/chip/configs/toy.json",
                             "reduced": [], "why": "discovery test"})
    bench["workloads"].append({"name": "toy.burst", "config": "toy",
                               "traffic": "burst", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] != "tpot_p95_ms":
            m["workloads"].append("toy.burst")
    bench["per_layer"].append({"name": "toy_waves", "unit": "waves",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine", "moves": "output_tok_s",
                               "workloads": ["toy.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    h = load_harness(bench_dir)
    bench, cell = h.load_cell(tmp_path, "toy.burst", 2 ** 40 + 1, 0.2, False)
    assert cell.config["model_config"]["d_model"] == SMALL["d_model"]
    h.import_program(tmp_path)
    cell.peak = cell.peaks["TPU v5 lite"]
    out = h.run_cell(bench, cell, jax.devices(), tmp_path)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"output_tok_s", "ttft_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    layer = h.metrics_of(bench, "toy.burst", "per_layer")
    assert [m["name"] for m in layer] == ["toy_waves"]
    reader = h.load_module(bench_dir / "metrics" / "toy_waves.py", "metric")
    assert reader.read({"records": [{}, {}]}, cell) == 2.0


@pytest.mark.parametrize("where,bad", [
    ("name", "granite 3"), ("name", "a/b"), ("name", "a,b"), ("name", "μs"),
    ("unit", "tokens per second"), ("unit", "µs"), ("unit", "")])
def test_refuses_names_and_units_out_of_contract(harness, where, bad):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    harness.validate(bench)
    if where == "name":
        bench["workloads"][0]["name"] = bad
    else:
        bench["end_to_end"][0]["unit"] = bad
    with pytest.raises(harness.Refused):
        harness.validate(bench)


def test_refuses_per_layer_metric_without_workloads(harness):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    del bench["per_layer"][0]["workloads"]
    with pytest.raises(harness.Refused, match="lists no workloads"):
        harness.validate(bench)


def test_refuses_repro_opts(harness, monkeypatch):
    _, cell = harness.load_cell(ROOT, "granite-3-2b.chat", 1, 1.0, False)
    monkeypatch.setenv("REPRO_OPTS", "kv_int8")
    with pytest.raises(harness.Refused, match="REPRO_OPTS"):
        harness.prepare_jax(ROOT, cell)


def _run(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **dict(extra_env))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "granite-3-2b.chat", "--seed", str(2 ** 31 + 9), "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_no_program_no_result(tmp_path):
    _checkout(tmp_path, with_program=False)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "not in this checkout" in p.stderr
    assert p.stdout.strip() == ""
