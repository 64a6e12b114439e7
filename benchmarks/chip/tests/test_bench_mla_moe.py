"""The DeepSeek-V2 share at a CPU size: its seeded weights, their mapping
into the program, the float32 reference against the program (prefill, then
decode through the cache, on logits), the fp8 control, the operation and
byte counts, and the MoE and latent-attention scopes."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import BENCH, harness, small_cell  # noqa: F401  (paths, fixtures)

import counts_mla_moe as counts
import moe_scopes
import scopes
import weights_mla_moe as W
from reference import mla_moe

SEED = 2 ** 35 + 5
CONFIG = BENCH / "configs" / "deepseek-v2-236b.ep8.json"
# every width cut; 16 experts in 4 groups, top 2 groups, top 3 experts
SMALL = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
             v_head_dim=16, rope_head_dim=8, kv_lora_rank=32, q_lora_rank=48,
             n_experts=16, n_group=4, topk_group=2, top_k=3, moe_d_ff=32,
             dense_d_ff=128, vocab_size=512)
SHARES = [pytest.param(0, 16, id="uncut"), pytest.param(4, 4, id="share-4-of-16")]


def small_config(first: int, held: int) -> dict:
    mc = json.loads(CONFIG.read_text())["model_config"]
    return dict(mc, **SMALL, experts_first=first, experts_held=held)


def program(mc: dict):
    """The program's model and its parameters (float32) from the seed."""
    from repro.configs.base import ModelConfig
    from repro.models import LanguageModel
    from entries.serve_waves_mla_moe import program_params

    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          program_params(mc, *W.make(mc, SEED)))
    return LanguageModel(ModelConfig(**mc)), params


def test_weights_equal_made_whole_or_by_layer():
    mc = small_config(4, 4)
    top, dense, moe = W.make(mc, SEED)
    for i in range(mc["n_layers"]):
        src, j = (dense, i) if i < mc["first_k_dense"] else (moe, i - mc["first_k_dense"])
        for k, v in W.layer(mc, SEED, i).items():
            np.testing.assert_array_equal(np.asarray(src[k][j]), np.asarray(v))
    for k, v in W.top(mc, SEED).items():
        np.testing.assert_array_equal(np.asarray(top[k]), np.asarray(v))
    assert moe["w_gate"].shape == (2, 4, 64, 32) and moe["router"].shape == (2, 64, 16)


def test_an_experts_weights_do_not_depend_on_the_share():
    whole = W.layer(small_config(0, 16), SEED, 1)
    part = W.layer(small_config(4, 4), SEED, 1)
    for k in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(np.asarray(whole[k][4:8]), np.asarray(part[k]))
    np.testing.assert_array_equal(np.asarray(whole["router"]), np.asarray(part["router"]))


def test_weight_mapping_fills_the_programs_tree():
    """Every program parameter is one benchmark leaf, of its shape."""
    from entries import serve_waves
    from entries.serve_waves_mla_moe import program_params

    mc = small_config(4, 4)
    model, params = program(mc)
    serve_waves._same_layout(jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), model)
    top, dense, moe = W.make(mc, SEED)
    mapped = program_params(mc, top, dense, moe)
    assert mapped["layers"]["attn"]["wkv_a_norm"]["scale"] is moe["wkv_a_norm"]
    assert mapped["layers"]["moe"]["shared"]["w_down"] is moe["shared_down"]
    assert mapped["dense_layers"]["ffn"]["w_up"] is dense["w_up"]
    assert mapped["emb"]["lm_head"] is top["lm_head"]


@pytest.mark.parametrize("first, held", SHARES)
def test_prefill_then_decode_logits_match_reference(first, held):
    """Token-at-a-time prefill then decode through the program's latent
    cache, in float32, against the reference's full forward. Both compute
    the same float32 mathematics in other orders (absorbed against expanded
    attention, grouped against per-expert matmuls), so they agree to a few
    float32 roundings of logits of unit size: 2e-4. A dropped latent norm,
    another rope pairing or YaRN, or another routing moves the logits by
    far more."""
    mc = small_config(first, held)
    model, params = program(mc)
    rng = np.random.default_rng(0)
    b, p, g, max_len = 2, 11, 6, 32
    tokens = rng.integers(0, mc["vocab_size"], (b, p + g)).astype(np.int32)
    cache = model.init_cache(b, max_len, dtype=jnp.float32)
    step = jax.jit(model.decode_step)
    got = []
    for t in range(p + g):
        logits, cache = step(params, cache, jnp.asarray(tokens[:, t:t + 1]), jnp.int32(t))
        got.append(np.asarray(logits[:, 0], np.float64))
    got = np.stack(got, axis=1)
    h, margin = mla_moe.hidden(mc, SEED, tokens)
    want = np.asarray(h, np.float64) @ np.asarray(W.top(mc, SEED)["lm_head"], np.float64)
    assert np.abs(want).max() > 0.5
    assert np.isfinite(np.asarray(margin)).all()
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_served_gaps_and_fp8_control():
    mc = small_config(4, 4)
    tokens = np.random.default_rng(2).integers(0, mc["vocab_size"], (2, 24)).astype(np.int32)
    rows = np.tile(np.arange(8, 24), (2, 1)).astype(np.int32)
    h, _ = mla_moe.hidden(mc, SEED, tokens)
    served = np.argmax(np.asarray(h)[np.arange(2)[:, None], rows] @ np.asarray(
        W.top(mc, SEED)["lm_head"]), axis=-1).astype(np.int32)
    gaps = mla_moe.served_gaps(mc, SEED, tokens, rows, served, control=True)
    assert gaps["gap"].max() < 1e-5          # the reference's own choices
    assert gaps["control_gap"].max() > 1e-2  # fp8 picks other tokens
    assert gaps["margin"].shape == rows.shape and (gaps["margin"] >= 0).all()


# ---- counts ------------------------------------------------------------------------

# d 64, 4 heads of 16 (rope 8, v 16), q latent 48, kv latent 32; 3 layers of
# which 1 dense (F 128); 16 experts of 32, 4 held, top 3; 2 shared; vocab 512
CFG = dict(SMALL, first_k_dense=1, n_shared_experts=2, experts_held=4)


def test_attn_params_by_hand():
    # wq_a 64*48 + wq_b 48*4*24 + wkv_a 64*40 + wk_b 32*64 + wv_b 32*64 + wo 64*64
    assert counts.attn_params(CFG) == 3072 + 4608 + 2560 + 2048 + 2048 + 4096


def test_token_params_by_hand():
    attn = 18432
    dense = 3 * 64 * 128
    moe = 64 * 16 + 3 * 64 * 64        # router, shared experts (2 x 32 wide)
    assert counts.token_params(CFG) == 3 * attn + dense + 2 * moe + 64 * 512


def test_experts_by_hand():
    # 5 tokens: each of 3 picks lands on a held expert with chance 4/16
    assert counts.routed_pairs(CFG, 5) == pytest.approx(5 * 3 * 4 / 16)
    assert counts.experts_touched(CFG, 5) == pytest.approx(4 * (1 - (13 / 16) ** 5))
    per_expert = 3 * 64 * 32
    assert counts.expert_flops(CFG, 5) == pytest.approx(2 * 3.75 * per_expert * 2)
    assert counts.expert_bytes(CFG, 5) == pytest.approx(
        2 * 2 * (4 * (1 - (13 / 16) ** 5) * per_expert + 3.75 * 2 * 64))


@pytest.mark.parametrize("pos", [0, 9])
def test_decode_flops_and_bytes_by_hand(pos):
    b = 5
    attn = 2 * (pos + 1) * 4 * (2 * 32 + 8) * 3      # scores and latent sum
    assert counts.decode_flops(CFG, b, pos) == pytest.approx(
        b * (2 * counts.token_params(CFG) + attn) + counts.expert_flops(CFG, b))
    norms = 3 * (2 * 64 + 48 + 32) + 64
    cache = b * (pos + 1) * 3 * 40 * 2
    assert counts.decode_bytes(CFG, b, pos) == pytest.approx(
        (counts.token_params(CFG) + norms + b * 64) * 2
        + counts.expert_bytes(CFG, b) + cache)


# ---- scopes ------------------------------------------------------------------------

def test_decode_step_carries_the_moe_and_latent_scopes(small_cell):
    _, cell = small_cell("deepseek-v2-236b.ep8.long_decode", **SMALL)
    cell.config["model_config"].update(experts_first=4, experts_held=4)
    paths = scopes.op_paths(scopes.decode_hlo(cell))
    parts = {moe_scopes.part_of(p) for p in paths.values()}
    assert parts >= {"route", "dispatch", "experts", "shared", "latent_core"}
    # each lies inside the scope that decode_scope_ms already reads
    for p in paths.values():
        part = moe_scopes.part_of(p)
        if part == "latent_core":
            assert scopes.part_of(p) == "attention", p
        elif part:
            assert scopes.part_of(p) == "ffn", p


def test_part_ms_of_a_hand_made_trace():
    paths = {"a": "jit(decode_step)/layers/ffn/moe_experts/dot",
             "b": "jit(decode_step)/layers/attention/latent_core/dot",
             "c": "jit(decode_step)/layers/ffn/dot"}
    trace = {"devices": {"/device:TPU:0": {
        "XLA Modules": [["jit_decode_step(1)", 0, 1000], ["jit_decode_step(1)", 2000, 1000]],
        "XLA Ops": [["a", 0, 300], ["b", 300, 100], ["c", 400, 500],
                    ["a", 2000, 500], ["b", 2500, 300]]}}}
    ms = moe_scopes.part_ms(trace, paths)
    assert ms["experts"] == pytest.approx(400e-6)
    assert ms["latent_core"] == pytest.approx(200e-6)
    assert ms["route"] == 0.0
    assert moe_scopes.part_ms(trace, {"a": "x/ffn/dot", "b": "x", "c": "x"}) is None


# ---- correct -----------------------------------------------------------------------

def _small_deepseek(small_cell):
    bench, cell = small_cell("deepseek-v2-236b.ep8.long_decode", **SMALL)
    cell.config["model_config"].update(experts_first=4, experts_held=4)
    cell.traffic["window_opens"] = "wave_start"
    return bench, cell


def test_sound_run_is_correct_and_control_is_not(harness, small_cell):
    _, cell = _small_deepseek(small_cell)
    entry = harness.load_module(harness.HERE / "entries" / "serve_waves_mla_moe.py", "entry")
    run = entry.run(cell, control=True)
    limit = cell.limits["logit_gap_over_share"]["limit"]
    assert run["numbers"]["logit_gap_over_share"] <= limit
    assert run["numbers"]["control_logit_gap_over_share"] > limit
    assert run["numbers"]["max_logit_gap"] >= run["numbers"]["logit_gap_p90"]


def _altered_token_sparse(make):
    """Every twentieth position's token, one id off, in every row."""
    def wrapped(model, **kw):
        step = make(model, **kw)

        def decode_step(params, cache, tokens, pos, rng):
            nxt, cache = step(params, cache, tokens, pos, rng)
            bad = (nxt + 1) % model.cfg.vocab_size
            return jnp.where(pos % 20 == 2, bad, nxt), cache
        return decode_step
    return wrapped


@pytest.mark.parametrize("fault", ["token_altered", "token_altered_sparse",
                                   "state_unchanged"])
def test_fault_in_timed_path_is_not_correct(harness, small_cell, monkeypatch, fault):
    """The dense cells' planted faults (``test_bench_faults.py``), in the
    DeepSeek cell: every fifth position's token one id off, or a step that
    returns the cache it was given; and a sparse fault, every twentieth
    position's token one id off."""
    import repro.launch.serve as serve
    from test_bench_faults import _altered_token, _state_unchanged

    make = {"token_altered": _altered_token, "state_unchanged": _state_unchanged,
            "token_altered_sparse": _altered_token_sparse}[fault]
    monkeypatch.setattr(serve, "make_decode_step", make(serve.make_decode_step))
    bench, cell = _small_deepseek(small_cell)
    out = harness.run_cell(bench, cell, jax.devices(), harness.ROOT)
    assert out["correct"] is False
    check = out["checks"]["logit_gap_over_share"]
    assert check["value"] > check["limit"]
