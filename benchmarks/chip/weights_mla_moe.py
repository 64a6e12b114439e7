"""Seeded weights of a DeepSeek-V2-style decoder (latent attention, leading
dense layers, then routed and shared experts), in the benchmark's own
layout, for the experts one chip holds.

As in ``weights.py``: the same seed gives the same weights, bit for bit,
whether made all at once for the program (``make``, one jitted call, in
bfloat16) or one layer at a time for the reference (``layer``, ``top``), and
every value comes from ``jax.random.uniform``. An expert's weights depend on
its index among all ``n_experts``, not on the share that holds it, so the
shares of one seed are parts of one model.

Layout (``Ld`` leading dense layers, ``Lm`` MoE layers, ``E`` routed
experts of which ``held`` are here, from ``experts_first``)::

    embedding (V, d)   lm_head (d, V)   final_norm (d,)
    every layer:  attn_norm (d,)  wq_a (d, ql)  wq_a_norm (ql,)
                  wq_b (ql, H*(hd+r))  wkv_a (d, kvl+r)  wkv_a_norm (kvl,)
                  wk_b (kvl, H*hd)  wv_b (kvl, H*vd)  wo (H*vd, d)
                  ffn_norm (d,)
    dense layers: w_gate, w_up (d, F)  w_down (F, d)
    MoE layers:   router (d, E)  w_gate, w_up (held, d, f)
                  w_down (held, f, d)  shared_gate, shared_up (d, S*f)
                  shared_down (S*f, d)

Each query head's columns of ``wq_b`` are its 128 no-rope then its 64 rope
dims, and ``wkv_a``'s are the latent then the shared rope key, as published.
A matrix has standard deviation 1/sqrt(fan_in), the embedding and the head
1/sqrt(d), and a norm scale lies in [0.8, 1.2].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import _frozen, _leaf, base_key

ATTN_LEAVES = ("attn_norm", "wq_a", "wq_a_norm", "wq_b", "wkv_a", "wkv_a_norm",
               "wk_b", "wv_b", "wo", "ffn_norm")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
MOE_LEAVES = ("router", "shared_gate", "shared_up", "shared_down")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def held(cfg: dict) -> tuple[int, int]:
    """(first, count) of the experts this chip holds."""
    return cfg.get("experts_first", 0), cfg.get("experts_held") or cfg["n_experts"]


def shapes(cfg: dict) -> tuple[dict, dict, dict, dict, dict]:
    """Leaf shapes: (top, every layer's attention, a dense layer's FFN, a
    MoE layer's router and shared experts, one expert's)."""
    d, h, hd, vd = cfg["d_model"], cfg["n_heads"], cfg["head_dim"], cfg["v_head_dim"]
    r, ql, kvl = cfg["rope_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, sf, fd = cfg["moe_d_ff"], cfg["moe_d_ff"] * cfg["n_shared_experts"], cfg["dense_d_ff"]
    top = {"embedding": (cfg["vocab_size"], d), "lm_head": (d, cfg["vocab_size"]),
           "final_norm": (d,)}
    attn = {"attn_norm": (d,), "wq_a": (d, ql), "wq_a_norm": (ql,),
            "wq_b": (ql, h * (hd + r)), "wkv_a": (d, kvl + r), "wkv_a_norm": (kvl,),
            "wk_b": (kvl, h * hd), "wv_b": (kvl, h * vd), "wo": (h * vd, d),
            "ffn_norm": (d,)}
    dense = {"w_gate": (d, fd), "w_up": (d, fd), "w_down": (fd, d)}
    moe = {"router": (d, cfg["n_experts"]), "shared_gate": (d, sf),
           "shared_up": (d, sf), "shared_down": (sf, d)}
    expert = {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    return top, attn, dense, moe, expert


def _top(cfg: dict, base):
    top = shapes(cfg)[0]
    return {n: _leaf(jax.random.fold_in(base, i), n, s, cfg["d_model"])
            for i, (n, s) in enumerate(sorted(top.items()))}


def _key(base, group: int, i: int, index):
    return jax.random.fold_in(jax.random.fold_in(base, group + i), index)


def _layer(cfg: dict, base, index, dense: bool):
    """Layer ``index`` (0-based over the whole stack)."""
    _, attn, dense_s, moe_s, expert_s = shapes(cfg)
    d = cfg["d_model"]
    out = {n: _leaf(_key(base, 100, i, index), n, attn[n], d)
           for i, n in enumerate(ATTN_LEAVES)}
    if dense:
        out.update({n: _leaf(_key(base, 200, i, index), n, dense_s[n], d)
                    for i, n in enumerate(DENSE_LEAVES)})
        return out
    out.update({n: _leaf(_key(base, 300, i, index), n, moe_s[n], d)
                for i, n in enumerate(MOE_LEAVES)})
    first, count = held(cfg)

    def expert(e):
        return {n: _leaf(jax.random.fold_in(_key(base, 400, i, index), e), n,
                         expert_s[n], d)
                for i, n in enumerate(EXPERT_LEAVES)}

    out.update(jax.vmap(expert)(jnp.arange(first, first + count, dtype=jnp.uint32)))
    return out


@functools.lru_cache(maxsize=None)
def _compiled(frozen: tuple):
    cfg = dict(frozen)
    kd = cfg["first_k_dense"]

    def make_all(base):
        def stack(lo, hi, dense):
            return jax.lax.map(lambda i: _layer(cfg, base, i, dense),
                               jnp.arange(lo, hi, dtype=jnp.uint32))
        return _top(cfg, base), stack(0, kd, True), stack(kd, cfg["n_layers"], False)

    return (jax.jit(make_all), jax.jit(lambda b: _top(cfg, b)),
            jax.jit(lambda b, i: _layer(cfg, b, i, True)),
            jax.jit(lambda b, i: _layer(cfg, b, i, False)))


def make(cfg: dict, seed: int):
    """(top, dense layers, MoE layers): every weight, each layer kind
    stacked, made on the default device in one call."""
    return _compiled(_frozen(cfg))[0](base_key(seed))


def top(cfg: dict, seed: int) -> dict:
    """The embedding, final norm and head alone."""
    return _compiled(_frozen(cfg))[1](base_key(seed))


def layer(cfg: dict, seed: int, index: int) -> dict:
    """Layer ``index``'s weights, equal to ``make``'s slice of its kind."""
    dense = index < cfg["first_k_dense"]
    return _compiled(_frozen(cfg))[2 if dense else 3](base_key(seed),
                                                      np.uint32(index))
