"""The trace reduction: busy and idle time, program runs, collective time,
idle gaps named by host spans, and the per-layer readers on top of them.

A hand-made trace checks each number against a count by hand; a short
trace recorded on a TPU v5e (``data/trace_chat.json``: the first 0.6 s of
the chat cell's traced window, reduced by ``devtrace.reduce_xplane``)
checks that the reduction finds what a real trace holds.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import pytest

import bench_helpers  # noqa: F401  (puts the paths in place)
import devtrace
from bench_helpers import harness, small_cell  # noqa: F401  (fixtures)
import schedule

DATA = Path(__file__).parent / "data"

# one chip; window 0-100 us; ops 10-30 and 25-40 overlap, 60-70 alone
HAND = {
    "window_ns": [0, 100_000],
    "devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_decode_step(1)", 10_000, 30_000],
                        ["jit_other(2)", 60_000, 10_000]],
        "XLA Ops": [["fusion.1", 10_000, 20_000], ["all-reduce.3", 25_000, 15_000],
                    ["copy.2", 60_000, 10_000]]}},
    "host": [["engine.generate", 0, 100_000], ["engine.prefill", 40_000, 15_000]],
}


def test_busy_and_window_by_hand():
    assert devtrace.window_s(HAND) == pytest.approx(100e-6)
    assert devtrace.busy_s(HAND) == pytest.approx(40e-6)  # 10-40 and 60-70


def test_program_runs_and_collectives_by_hand():
    runs = devtrace.program_runs(HAND, "decode_step")
    assert [r[0] for r in runs] == ["jit_decode_step(1)"]
    assert devtrace.collective_s(HAND) == pytest.approx(15e-6)


def test_top_ops_and_idle_gaps_by_hand():
    assert devtrace.top_ops(HAND, 2) == [["fusion.1", pytest.approx(20e-6)],
                                        ["all-reduce.3", pytest.approx(15e-6)]]
    # gaps 0-10, 40-60 (prefill span covers its middle), 70-100
    assert devtrace.idle_gaps(HAND) == [
        ["engine.generate", pytest.approx(30e-6)],
        ["engine.prefill", pytest.approx(20e-6)],
        ["engine.generate", pytest.approx(10e-6)]]


def test_traced_positions_follow_the_waves():
    records = [{"B": 4, "P": 3, "G": 2}, {"B": 4, "P": 2, "G": 3}]
    got = schedule.traced_positions(records, {"wave": 0, "pos": 1}, 10)
    assert got == [(4, 1), (4, 2), (4, 3), (4, 0), (4, 1), (4, 2), (4, 3)]


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_chat.json").read_text())


def test_recorded_trace_reduces(recorded):
    t = recorded["trace"]
    assert list(t["devices"]) == ["/device:TPU:0"]
    runs = devtrace.program_runs(t, "decode_step")
    assert len(runs) == 6
    busy, window = devtrace.busy_s(t), devtrace.window_s(t)
    assert 0.9 * window < busy <= window
    ops = devtrace.top_ops(t)
    assert len(ops) == 10 and ops[0][1] >= ops[-1][1] > 0
    assert not any(name.startswith("while") for name, _ in ops)
    gaps = devtrace.idle_gaps(t)
    assert gaps[0][0] == "engine.prefill"      # the window opens in prefill
    assert all(g[0] in devtrace.SPANS for g in gaps)
    assert devtrace.collective_s(t) == 0.0     # one chip


class _Cell:
    chips = 1
    peak = json.loads((DATA.parent.parent / "peaks.json").read_text())["TPU v5 lite"]
    config = json.loads((DATA.parent.parent / "configs" / "granite-3-2b.json").read_text())


def _read(name, recorded):
    import run as harness
    reader = harness.load_module(DATA.parent.parent / "metrics" / f"{name}.py", "metric")
    t = dict(recorded["trace"], first_step={"wave": 0, "pos": 0})
    return reader.read({"trace": t, "records": recorded["records"]}, _Cell)


def test_readers_on_recorded_trace(recorded):
    step = _read("decode_step_ms", recorded)
    assert 27.0 < step < 30.0
    # 6 steps at positions 0-5 of 32 requests: weights dominate the bytes
    roof = _read("decode_hbm_roofline", recorded)
    assert roof == pytest.approx(100 * 5.07e9 / 819e9 / (step / 1e3), rel=0.02)
    mfu = _read("serve_mfu", recorded)
    assert 0 < mfu < roof
    assert 0 <= _read("device_idle.serve", recorded) < 10


def test_tracer_stops_at_wave_end_before_its_timer(tmp_path):
    tracer = devtrace.Tracer(str(tmp_path / "trace"))
    t0 = time.perf_counter()
    tracer.start(600.0)
    tracer.stop()
    tracer.stop()          # the wave's end and the timer may both stop it
    tracer.join()
    assert time.perf_counter() - t0 < 60
    assert list(tmp_path.glob("trace/**/*.xplane.pb"))


class _FakeTracer:
    """Stands in for the profiler: records when it starts and stops."""

    def __init__(self):
        self.calls = []

    def start(self, seconds):
        self.calls.append(("start", time.perf_counter()))

    def stop(self):
        self.calls.append(("stop", time.perf_counter()))

    def join(self):
        pass

    def result(self):
        return {"devices": {}, "window_ns": [0, 1]}


@pytest.mark.parametrize("opens", ["wave_start", "first_token"])
def test_adapter_traces_the_wave_the_traffic_names(small_cell, harness, opens):
    bench, cell = small_cell()
    cell.trace, cell.tracer = True, _FakeTracer()
    cell.seconds = 1e-3
    cell.traffic["trace"] = {"wave": 1, "opens": opens, "seconds": 60.0}
    entry = harness.load_module(harness.HERE / "entries" / "serve_waves.py", "entry")
    run = entry.run(cell)
    assert len(run["records"]) == 2            # the loop ran on to wave 1
    assert [c for c, _ in cell.tracer.calls] == ["start", "stop"]
    (_, t_start), (_, t_stop) = cell.tracer.calls
    w = run["records"][1]
    t = run["trace"]
    assert t["first_step"] == {"wave": 1, "pos": w["P"] if opens == "first_token" else 0}
    # host spans on the profile's clock: the traced wave's generate span
    # starts at 0, or its prefill ends at 0
    gen = [h for h in t["host"] if h[0] == "engine.generate"][1]
    pre = [h for h in t["host"] if h[0] == "engine.prefill"][1]
    at_zero = gen[1] if opens == "wave_start" else pre[1] + pre[2]
    assert abs(at_zero) < 1e6                  # within 1 ms
    # stopped when the traced wave returned, long before its timer
    assert t_stop - t_start <= gen[2] / 1e9 + 0.05
