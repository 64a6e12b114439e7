"""Device time per named scope of the decode step (``scopes.py``).

By hand: the map from instruction to scope path, and the split of a
program's runs into parts. On the CPU: the decode step, compiled at a tiny
size, carries every scope the readers look for, and the readers' own
compile names its instructions as the engine's compile does. On a short
trace recorded on a TPU v5e (``data/trace_chat_spans.json``): the six parts
sum to ``decode_step_ms``, and the program's host spans share the device's
clock.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import pytest

import bench_helpers  # noqa: F401  (puts the paths in place)
import devtrace
import scopes
from bench_helpers import harness, small_cell  # noqa: F401  (fixtures)

DATA = Path(__file__).parent / "data"

HLO = """\
HloModule jit_decode_step, is_scheduled=true

%body (p: (s32[], bf16[4])) -> (s32[], bf16[4]) {
  %p = (s32[], bf16[4]{0}) parameter(0)
  %gte.1 = bf16[4]{0} get-tuple-element(%p), index=1
  %fusion.2 = bf16[4]{0} fusion(%gte.1), kind=kLoop, calls=%f, metadata={op_name="jit(decode_step)/layers/while/body/closed_call/ffn/dot_general" stack_frame_id=3}
  %dus.3 = bf16[4]{0} dynamic-update-slice(%fusion.2), metadata={op_name="jit(decode_step)/layers/while/body/closed_call/attention/cache_update/dynamic_update_slice"}
  ROOT %t = (s32[], bf16[4]{0}) tuple(%gte.1, %dus.3)
}

ENTRY %main (x: bf16[4]) -> bf16[4] {
  %x = bf16[4]{0} parameter(0)
  %while.4 = (s32[], bf16[4]{0}) while(%x), condition=%cond, body=%body, metadata={op_name="jit(decode_step)/layers/while"}
  %gte.5 = bf16[4]{0} get-tuple-element(%while.4), index=1, metadata={op_name="jit(decode_step)/layers/while"}
  %copy.6 = bf16[4]{0} copy(%gte.5), backend_config={"x":1}
  %fusion.7 = s32[4]{0} fusion(%copy.6), kind=kOutput, metadata={op_name="jit(decode_step)/logits_sample/argmax"}
  ROOT %gather.8 = bf16[4]{0} gather(%x), metadata={op_name="jit(decode_step)/embed/jit(_take)/gather"}
}
"""


def test_op_paths_and_parts_by_hand():
    paths = scopes.op_paths(HLO)
    assert paths["fusion.2"].endswith("/ffn/dot_general")
    assert paths["gte.1"] == ""                 # no metadata, operand neither
    # the compiler's copy of the loop's result takes its operand's path
    assert paths["copy.6"] == "jit(decode_step)/layers/while"
    parts = {n: scopes.part_of(p) for n, p in paths.items()}
    assert parts == {"p": "other", "gte.1": "other", "fusion.2": "ffn",
                     "dus.3": "cache_update", "t": "cache_update", "x": "other",
                     "while.4": "layer_loop", "gte.5": "layer_loop",
                     "copy.6": "layer_loop", "fusion.7": "logits_sample",
                     "gather.8": "other"}


def _trace(ops):
    return {"window_ns": [0, 100_000], "devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_decode_step(1)", 10_000, 30_000],
                        ["jit_other(2)", 45_000, 5_000],
                        ["jit_decode_step(1)", 50_000, 30_000]],
        "XLA Ops": ops}}}


PATHS = {"fusion.2": "a/layers/ffn/dot", "dus.3": "a/layers/attention/cache_update/d",
         "copy.6": "a/layers/while", "gather.8": "a/embed/gather",
         "fusion.9": "a/layers/attention/dot", "fusion.7": "a/logits_sample/max",
         "add.1": "b/add"}


def test_parts_of_the_runs_by_hand():
    # two runs of 30 us: ops 10+5+3+2+4+1 us in the first, 20 in the second;
    # the other program's op is not counted
    t = _trace([["gather.8", 10_000, 1_000], ["fusion.9", 11_000, 4_000],
                ["dus.3", 15_000, 3_000], ["fusion.2", 18_000, 10_000],
                ["copy.6", 28_000, 2_000], ["fusion.7", 30_000, 5_000],
                ["add.1", 45_000, 5_000], ["fusion.2", 50_000, 20_000]])
    got = scopes.part_ms(t, PATHS)
    assert got == pytest.approx({"attention": 0.002, "cache_update": 0.0015,
                                 "layer_loop": 0.001, "ffn": 0.015,
                                 "logits_sample": 0.0025, "other": 0.008})
    step_ms = 1e3 * sum(d for *_, d in devtrace.program_runs(t, "decode_step")) / 2 / 1e9
    assert sum(got.values()) == pytest.approx(step_ms)


def test_parts_read_nothing_without_scopes_or_with_another_program():
    t = _trace([["gather.8", 10_000, 1_000], ["add.1", 12_000, 5_000]])
    assert scopes.part_ms(t, PATHS) is None     # no scope in the runs
    t = _trace([["fusion.2", 10_000, 1_000], ["fusion.99", 12_000, 5_000]])
    assert scopes.part_ms(t, PATHS) is None     # an op the map lacks
    assert scopes.part_ms(_trace([]) | {"devices": {"/device:TPU:0": {}}},
                          PATHS) is None        # no run of the step


def test_scoped_decode_step_carries_every_scope(small_cell):
    _, cell = small_cell()
    paths = scopes.op_paths(scopes.decode_hlo(cell))
    parts = {scopes.part_of(p) for p in paths.values()}
    assert parts == set(scopes.PARTS)
    assert any("/embed/" in p for p in paths.values())
    dus = [p for n, p in paths.items() if n.startswith("dynamic-update-slice")
           or n.startswith("dynamic_update_slice")]
    assert dus and {scopes.part_of(p) for p in dus} <= {"cache_update", "layer_loop"}
    assert "cache_update" in {scopes.part_of(p) for p in dus}


def test_readers_compile_names_ops_as_the_engine_does(small_cell, harness):
    from repro.launch.mesh import make_host_mesh

    _, cell = small_cell()
    entry = harness.load_module(harness.HERE / "entries" / "serve_waves.py", "entry")
    with jax.sharding.set_mesh(make_host_mesh()):
        engine = entry.build(cell, cell.seed)
    assert scopes.op_paths(engine.decode.as_text()) == scopes.op_paths(
        scopes.decode_hlo(cell))


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_chat_spans.json").read_text())


def test_recorded_parts_sum_to_the_step(recorded, harness, monkeypatch):
    monkeypatch.setattr(scopes, "decode_hlo", lambda cell: "")
    monkeypatch.setattr(scopes, "op_paths", lambda hlo: recorded["paths"])
    run = {"trace": recorded["trace"]}
    read = {p: harness.load_module(
        harness.HERE / "metrics" / f"decode_scope_ms.{p}.py", "metric").read(run, None)
        for p in scopes.PARTS}
    step = harness.load_module(harness.HERE / "metrics" / "decode_step_ms.py",
                               "metric").read(run, None)
    assert sum(read.values()) == pytest.approx(step, abs=1e-6)
    assert all(read[p] > 0 for p in scopes.SCOPES.values())
    assert 0 <= read["other"] < 0.01       # every op of the step has an owner


def test_recorded_spans_share_the_devices_clock(recorded):
    t, spans = recorded["trace"], recorded["program_spans"]
    runs = devtrace.program_runs(t, "decode_step")
    steps, dispatch, fetch = ([s for s in spans if s[0] == n] for n in
                              ("serve.step", "serve.dispatch", "serve.fetch"))
    assert len(runs) == len(steps) == len(dispatch) == 6   # 3 prompt steps, 3 more
    assert [s[3]["pos"] for s in steps] == list(range(6))
    # each run starts after the dispatch that launched it began
    assert all(d[1] < r[1] for d, r in zip(dispatch, runs))
    # the last prompt step and each generated one end before their token's
    # fetch ends (earlier prompt steps run ahead of the host)
    assert len(fetch) == 4
    assert all(r[1] + r[2] < f[1] + f[2] for r, f in zip(runs[2:], fetch))
