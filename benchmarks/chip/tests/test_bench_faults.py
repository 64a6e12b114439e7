"""``correct`` is true for a sound run and false for the control and for
each fault that a serving cell can have, planted in the timed path.

The runs skip the harness's look for a chip and drive the rest of a run on
the CPU at a small size, against each serving cell's own limit. The control
is the reference in the program's place one precision below bfloat16 (fp8
operands), read at the positions of the served tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench_helpers import small_cell  # noqa: F401  (fixture)
from bench_helpers import harness  # noqa: F401  (fixture)

CELLS = ["granite-3-2b.chat", "mistral-nemo-12b.pp4.reasoning"]


def _untied(workload):
    return {"tie_embeddings": False} if "nemo" in workload else {}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_control_is_not(harness, small_cell, workload):
    bench, cell = small_cell(workload, **_untied(workload))
    cell.traffic["window_opens"] = "wave_start"
    entry = harness.load_module(harness.HERE / "entries" / "serve_waves.py", "entry")
    run = entry.run(cell, control=True)
    limit = cell.limits["max_logit_gap"]["limit"]
    assert run["numbers"]["max_logit_gap"] <= limit
    assert run["numbers"]["control_max_logit_gap"] > limit


def _altered_token(make):
    """Every fifth position's token, one id off, in every row."""
    def wrapped(model, **kw):
        step = make(model, **kw)

        def decode_step(params, cache, tokens, pos, rng):
            nxt, cache = step(params, cache, tokens, pos, rng)
            bad = (nxt + 1) % model.cfg.vocab_size
            return jnp.where(pos % 5 == 2, bad, nxt), cache
        return decode_step
    return wrapped


def _state_unchanged(make):
    """The step returns the cache it was given."""
    def wrapped(model, **kw):
        step = make(model, **kw)

        def decode_step(params, cache, tokens, pos, rng):
            nxt, _ = step(params, cache, tokens, pos, rng)
            return nxt, jax.tree.map(lambda a: a + 0, cache)
        return decode_step
    return wrapped


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_in_timed_path_is_not_correct(harness, small_cell, monkeypatch, fault):
    import repro.launch.serve as serve

    monkeypatch.setattr(serve, "make_decode_step", fault(serve.make_decode_step))
    bench, cell = small_cell()
    out = harness.run_cell(bench, cell, jax.devices(), harness.ROOT)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > out["checks"]["max_logit_gap"]["limit"]
