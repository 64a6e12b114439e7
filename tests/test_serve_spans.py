"""The serving engine's host spans, read back from a real profile.

``ServingEngine`` writes ``serve.*`` spans with ``jax.profiler``
annotations; they land in the profile's host plane, on the clock of the
device's events. These tests record a profile of a tiny engine on the CPU,
with the options the on-chip benchmark profiles with, and read the spans
back as a reader of that profile would.
"""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.launch.serve import ServingEngine
from repro.models import LanguageModel

BATCH, PROMPT, GEN = 2, 3, 4


def _spans(path: str) -> list[tuple[str, float, float, dict]]:
    """Host-plane events named ``serve.*``: (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     {k: v for k, v in e.stats if not k.startswith("_")})
                    for line in plane.lines for e in line.events
                    if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _profile(directory, fn) -> list[tuple[str, float, float, dict]]:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    return _spans(paths[0])


@pytest.fixture(scope="module")
def engine():
    cfg = C.get("tinyllama-1.1b-smoke")
    model = LanguageModel(cfg)
    eng = ServingEngine(model, model.init(jax.random.PRNGKey(0)), BATCH, 16)
    eng.compile()
    # warm the host-side programs (position and key) outside any profile
    eng.generate(np.ones((BATCH, 2), np.int32), 2)
    return eng


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_generate_writes_nested_serve_spans(engine, tmp_path):
    prompts = np.arange(BATCH * PROMPT, dtype=np.int32).reshape(BATCH, PROMPT)
    steps_before = engine.steps
    spans = _profile(tmp_path, lambda: engine.generate(prompts, GEN))
    by = {n: [s for s in spans if s[0] == n] for n in
          ("serve.generate", "serve.prefill", "serve.step", "serve.dispatch",
           "serve.fetch", "serve.compile")}
    (gen,), (pre,) = by["serve.generate"], by["serve.prefill"]
    assert _inside(pre, gen)
    # one step per decode-step call: P prompt steps, then G - 1 generated
    steps = by["serve.step"]
    assert len(steps) == PROMPT + GEN - 1
    assert [s[3]["step_num"] for s in steps] == list(
        range(steps_before, steps_before + len(steps)))
    assert [s[3]["pos"] for s in steps] == list(range(PROMPT + GEN - 1))
    assert all(_inside(s, pre) for s in steps[:PROMPT])
    assert all(_inside(s, gen) and not _inside(s, pre) for s in steps[PROMPT:])
    # each step holds its dispatch; the generated ones their fetch too
    dispatch, fetch = by["serve.dispatch"], by["serve.fetch"]
    assert len(dispatch) == len(steps)
    assert all(_inside(d, s) for d, s in zip(dispatch, steps))
    assert len(fetch) == GEN                    # one per generated token
    assert _inside(fetch[0], gen) and fetch[0][1] >= pre[2]
    assert all(_inside(f, s) for f, s in zip(fetch[1:], steps[PROMPT:]))
    assert by["serve.compile"] == []            # nothing compiled here


def test_compile_inside_the_profile_is_marked(engine, tmp_path):
    fresh = jax.jit(lambda x: jnp.sin(x) * 3.0)   # never compiled before
    x = jnp.ones(7).block_until_ready()
    spans = _profile(tmp_path, lambda: fresh(x).block_until_ready())
    marks = [s for s in spans if s[0] == "serve.compile"]
    assert len(marks) == 1
    _, start, end, stats = marks[0]
    assert end - start < 1e6 and stats["seconds"] > 0    # a marker, not a span
