"""Seeded weights of a dense GQA decoder, in the benchmark's own layout.

The same seed gives the same weights, bit for bit, whether they are made
all at once for the program (``make``, one jitted call on the device, in
bfloat16) or one layer at a time for the reference (``layer``, ``top``).
Every value comes from ``jax.random.uniform``, which is integer bits turned
into floats by exact arithmetic, so no fused transcendental can round
differently in the two programs.

Layout (``L`` layers, widths as the configuration states)::

    embedding (V, d)   lm_head (d, V), untied only   final_norm (d,)
    layers: attn_norm (L, d)  wq (L, d, H*hd)  wk, wv (L, d, KV*hd)
            wo (L, H*hd, d)   ffn_norm (L, d)  w_gate, w_up (L, d, F)
            w_down (L, F, d)

A matrix has standard deviation 1/sqrt(fan_in), the embedding and the head
1/sqrt(d) (logits of about unit spread), and a norm scale lies in
[0.8, 1.2], so that a program that skipped a scale would not agree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm",
                "w_gate", "w_up", "w_down")


def shapes(cfg: dict) -> tuple[dict, dict]:
    """(top-level leaf shapes, per-layer leaf shapes) of a model_config."""
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    f, v = cfg["d_ff"], cfg["vocab_size"]
    top = {"embedding": (v, d), "final_norm": (d,)}
    if not cfg["tie_embeddings"]:
        top["lm_head"] = (d, v)
    layer = {"attn_norm": (d,), "wq": (d, h * hd), "wk": (d, kv * hd),
             "wv": (d, kv * hd), "wo": (h * hd, d), "ffn_norm": (d,),
             "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return top, layer


def base_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, also one over 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _leaf(key, name: str, shape, d_model: int):
    if name.endswith("norm"):
        lo, hi = 0.8, 1.2
    else:
        fan_in = d_model if name == "embedding" else shape[0]
        a = float(np.sqrt(3.0 / fan_in))
        lo, hi = -a, a
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(jnp.bfloat16)


def _top(cfg: dict, base):
    top, _ = shapes(cfg)
    return {n: _leaf(jax.random.fold_in(base, i), n, s, cfg["d_model"])
            for i, (n, s) in enumerate(sorted(top.items()))}


def _layer(cfg: dict, base, index):
    _, layer = shapes(cfg)
    return {n: _leaf(jax.random.fold_in(jax.random.fold_in(base, 100 + i), index),
                     n, layer[n], cfg["d_model"])
            for i, n in enumerate(LAYER_LEAVES)}


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (list, dict))))


@functools.lru_cache(maxsize=None)
def _compiled(frozen: tuple):
    cfg = dict(frozen)

    def make_all(base):
        layers = jax.lax.map(lambda i: _layer(cfg, base, i),
                             jnp.arange(cfg["n_layers"], dtype=jnp.uint32))
        return _top(cfg, base), layers

    return (jax.jit(make_all), jax.jit(lambda b: _top(cfg, b)),
            jax.jit(lambda b, i: _layer(cfg, b, i)))


def make(cfg: dict, seed: int):
    """(top, layers): every weight, made on the default device in one call."""
    return _compiled(_frozen(cfg))[0](base_key(seed))


def top(cfg: dict, seed: int) -> dict:
    """The embedding, final norm and (untied) head alone."""
    return _compiled(_frozen(cfg))[1](base_key(seed))


def layer(cfg: dict, seed: int, index: int) -> dict:
    """One layer's weights, equal to ``make``'s slice ``index``."""
    return _compiled(_frozen(cfg))[2](base_key(seed), jnp.uint32(index))
