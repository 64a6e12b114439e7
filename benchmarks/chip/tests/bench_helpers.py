"""Shared set-up of the benchmark's CPU tests: the benchmark's directory on
the import path, the program's ``src`` too, and a small cell.

The small cell keeps a real cell's files (configuration, traffic, limits)
and shrinks only what a CPU can hold: widths, depth, vocabulary, batch and
lengths.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMALL = dict(n_layers=4, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab_size=1024)


def load_harness(bench_dir: Path = BENCH):
    """``run.py`` of a benchmark directory, as a module of its own."""
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(bench_dir)))}", bench_dir / "run.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def harness():
    return load_harness()


@pytest.fixture
def small_cell(harness):
    """The chat cell at CPU size: (bench, cell)."""
    def make(workload="granite-3-2b.chat", seed=2 ** 33 + 17, **cfg):
        # a window of 1 ms: wave 0 alone, whatever the CPU's speed
        bench, cell = harness.load_cell(harness.ROOT, workload, seed, 1e-3, False)
        cell.config["model_config"].update(SMALL, **cfg)
        cell.traffic.update(batch=8, max_len=64, check_requests=8,
                            waves=[[8, 56], [21, 9]])
        cell.peak = cell.peaks["TPU v5 lite"]
        harness.import_program(harness.ROOT)
        return bench, cell
    return make
