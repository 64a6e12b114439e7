"""The float32 reference against the program at a CPU size, and the seeded
weights: prefill-then-decode logits through the program's cache, the
training loss and its gradients, and weights equal whichever way made."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_helpers import SMALL, ROOT  # noqa: F401  (puts the paths in place)

import weights as W
from reference import dense_gqa

SEED = 2 ** 35 + 3
TIED = [pytest.param(True, id="tied"), pytest.param(False, id="untied")]


def small_config(tied: bool) -> dict:
    return dict(SMALL, name="small", family="dense", tie_embeddings=tied,
                rope_theta=10000.0 if tied else 1e6, norm_eps=1e-5,
                dtype="bfloat16")


def program(mc: dict):
    """The program's model and its parameters (float32) from the seed."""
    from repro.configs.base import ModelConfig
    from repro.models import LanguageModel
    from entries.serve_waves import program_params

    top, layers = W.make(mc, SEED)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          program_params(mc, top, layers))
    return LanguageModel(ModelConfig(**mc)), params


def test_weights_equal_made_whole_or_by_layer():
    mc = small_config(tied=False)
    top, layers = W.make(mc, SEED)
    for i in range(mc["n_layers"]):
        one = W.layer(mc, SEED, i)
        for k, v in one.items():
            np.testing.assert_array_equal(np.asarray(layers[k][i]), np.asarray(v))
    for k, v in W.top(mc, SEED).items():
        np.testing.assert_array_equal(np.asarray(top[k]), np.asarray(v))
    assert top["embedding"].dtype == jnp.bfloat16
    other = W.make(mc, SEED + 1)[0]["embedding"]
    assert not np.array_equal(np.asarray(other), np.asarray(top["embedding"]))


@pytest.mark.parametrize("tied", TIED)
def test_prefill_then_decode_logits_match_reference(tied):
    mc = small_config(tied)
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
    h = np.asarray(dense_gqa.hidden(mc, SEED, tokens), np.float64)
    top = W.top(mc, SEED)
    want = h @ np.asarray(dense_gqa.head_weight(mc, top), np.float64)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("tied", TIED)
def test_training_loss_and_gradients_match_reference(tied):
    mc = small_config(tied)
    model, params = program(mc)
    rng = np.random.default_rng(1)
    seq = rng.integers(0, mc["vocab_size"], (2, 17)).astype(np.int32)
    batch = {"tokens": jnp.asarray(seq[:, :-1]), "labels": jnp.asarray(seq[:, 1:])}
    loss_p, grads_p = jax.value_and_grad(model.loss)(params, batch)

    top, layers = W.make(mc, SEED)
    ref_top = {k: v.astype(jnp.float32) for k, v in top.items()}
    ref_layers = [{k: v[i].astype(jnp.float32) for k, v in layers.items()}
                  for i in range(mc["n_layers"])]

    def ref_loss(t, ls):
        return dense_gqa.loss(mc, t, ls, batch["tokens"], batch["labels"])

    loss_r, (g_top, g_layers) = jax.value_and_grad(ref_loss, argnums=(0, 1))(
        ref_top, ref_layers)
    np.testing.assert_allclose(float(loss_p), float(loss_r), rtol=1e-5)

    pairs = [(grads_p["emb"]["embedding"], g_top["embedding"]),
             (grads_p["ln_f"]["scale"], g_top["final_norm"])]
    if not tied:
        pairs.append((grads_p["emb"]["lm_head"], g_top["lm_head"]))
    names = {"attn_norm": ("ln1", "scale"), "ffn_norm": ("ln2", "scale"),
             "wq": ("attn", "wq"), "wk": ("attn", "wk"), "wv": ("attn", "wv"),
             "wo": ("attn", "wo"), "w_gate": ("ffn", "w_gate"),
             "w_up": ("ffn", "w_up"), "w_down": ("ffn", "w_down")}
    for k, (a, b) in names.items():
        for i in range(mc["n_layers"]):
            pairs.append((grads_p["layers"][a][b][i], g_layers[i][k]))
    for got, want in pairs:
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        assert np.linalg.norm(want) > 0
        assert np.linalg.norm(got - want) <= 1e-4 * np.linalg.norm(want)


def test_fp8_control_departs_from_reference():
    mc = small_config(tied=True)
    tokens = np.random.default_rng(2).integers(0, mc["vocab_size"], (2, 24)).astype(np.int32)
    rows = np.tile(np.arange(8, 24), (2, 1)).astype(np.int32)
    h = np.asarray(dense_gqa.hidden(mc, SEED, tokens))
    served = np.argmax(h[np.arange(2)[:, None], rows] @ np.asarray(
        dense_gqa.head_weight(mc, W.top(mc, SEED))), axis=-1).astype(np.int32)
    gaps = dense_gqa.served_gaps(mc, SEED, tokens, rows, served, control=True)
    assert gaps["gap"].max() < 1e-5          # the reference's own choices
    assert gaps["control_gap"].max() > 1e-2  # fp8 picks other tokens
