"""Plain float32 reference of a DeepSeek-V2 decoder on one chip's share of
its experts.

Written from the published description (arXiv:2405.04434 and the model's
``modeling_deepseek.py``), not from the program:

* token embedding;
* per layer, RMSNorm, then multi-head latent attention in the EXPANDED form:
  the query goes through its latent, ``q = RMSNorm(x Wq_a) Wq_b``, split per
  head into 128 no-rope and 64 rope dims; the KV latent ``c = RMSNorm(x
  Wkv_a[:, :512])`` and one shared rope key ``k_pe = x Wkv_a[:, 512:]``;
  every head's key ``[c Wk_b, k_pe]`` and value ``c Wv_b`` rebuilt from the
  latent at every position; YaRN rope on the rope dims, rotating
  interleaved pairs (2i, 2i + 1); causal softmax scaled by
  ``(128 + 64) ** -0.5 * mscale(40, 0.707) ** 2``; output projection;
  residual;
* RMSNorm, then the FFN: SwiGLU in the leading dense layers; in the others
  the router (float32): softmax over all routed experts, group-limited
  greedy (a group's score is its best expert's; the best ``topk_group``
  groups stay; top-k among their experts), weights not renormalised and
  multiplied by ``routed_scaling_factor``; the experts this chip holds
  contribute, each ``weight * SwiGLU_e(x)``, the others are left out, as in
  the program; plus the shared experts, one SwiGLU of width
  ``moe_d_ff * n_shared_experts``; residual;
* final RMSNorm; logits against the untied head.

Every norm uses ``norm_eps`` (DeepSeek-V2: 1e-6 for all of them). It runs
under ``jax.default_matmul_precision("highest")`` and takes its weights from
``weights_mla_moe.py`` one layer at a time. Queries are attended in blocks
of ``Q_BLOCK`` rows and logits are formed in blocks of ``ROW_BLOCK``.

Beside each layer's output it keeps the router's margins at every
position: in router logits, between the last expert chosen and the next,
and between the last group kept and the next. A margin under the bfloat16
program's error can route the program otherwise.

``quant="fp8"`` is the control: every matrix product of a projection, an
expert, the shared experts, the dense FFN and the head takes float8_e4m3fn
operands (``dense_gqa._mm``); the router stays float32.
"""
from __future__ import annotations

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import weights_mla_moe as W  # noqa: E402
from reference.dense_gqa import _frozen, _mm, _take_rows, rmsnorm  # noqa: E402

Q_BLOCK = 256
ROW_BLOCK = 256


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """Rope inverse frequencies of the rope dims, YaRN-scaled as published
    (``DeepseekV2YarnRotaryEmbedding``), in float64."""
    dim, base = cfg["rope_head_dim"], cfg["rope_theta"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor = cfg.get("yarn_factor", 1.0)
    if factor <= 1.0:
        return extra
    orig = cfg["yarn_original_max_position"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(cfg["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(cfg["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return extra / factor * (1.0 - mask) + extra * mask


def rope_scale(cfg: dict) -> float:
    """YaRN's factor on cos and sin, mscale(f, mscale) / mscale(f, all)."""
    f = cfg.get("yarn_factor", 1.0)
    return yarn_get_mscale(f, cfg.get("yarn_mscale", 1.0)) / yarn_get_mscale(
        f, cfg.get("yarn_mscale_all_dim", 0.0))


def softmax_scale(cfg: dict) -> float:
    scale = (cfg["head_dim"] + cfg["rope_head_dim"]) ** -0.5
    f, m = cfg.get("yarn_factor", 1.0), cfg.get("yarn_mscale_all_dim", 0.0)
    if f > 1.0 and m:
        scale *= yarn_get_mscale(f, m) ** 2
    return scale


def rope(cfg: dict, x, positions):
    """x (..., S, heads, r): rotate each interleaved pair (2i, 2i + 1)."""
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = positions[..., :, None].astype(jnp.float32) * inv
    cos = jnp.cos(ang)[..., None, :] * rope_scale(cfg)
    sin = jnp.sin(ang)[..., None, :] * rope_scale(cfg)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def causal_attention(q, k, v, scale):
    """q, k (n, S, H, dq), v (n, S, H, dv) -> (n, S, H, dv), causal."""
    n, s, h, _ = q.shape
    qb = min(Q_BLOCK, s)
    nb = -(-s // qb)
    q = jnp.pad(q, ((0, 0), (0, nb * qb - s), (0, 0), (0, 0)))
    qblocks = q.reshape(n, nb, qb, h, -1).swapaxes(0, 1)
    cols = jnp.arange(s)

    def block(args):
        i, qi = args
        scores = jnp.einsum("nqhd,nkhd->nhqk", qi, k) * scale
        rows = i * qb + jnp.arange(qb)
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(block, (jnp.arange(nb), qblocks))
    return out.swapaxes(0, 1).reshape(n, nb * qb, h, -1)[:, :s]


def attention(cfg: dict, w: dict, y, quant: str):
    """Latent attention of normed inputs y (n, S, d), expanded per head."""
    n, s, _ = y.shape
    h, hd, r, vd = cfg["n_heads"], cfg["head_dim"], cfg["rope_head_dim"], cfg["v_head_dim"]
    kvl, eps = cfg["kv_lora_rank"], cfg["norm_eps"]
    pos = jnp.broadcast_to(jnp.arange(s), (n, s))
    cq = rmsnorm(_mm(y, w["wq_a"], quant), w["wq_a_norm"], eps)
    q = _mm(cq, w["wq_b"], quant).reshape(n, s, h, hd + r)
    kv_a = _mm(y, w["wkv_a"], quant)
    c = rmsnorm(kv_a[..., :kvl], w["wkv_a_norm"], eps)
    k_pe = rope(cfg, kv_a[:, :, None, kvl:], pos)
    k_nope = _mm(c, w["wk_b"], quant).reshape(n, s, h, hd)
    v = _mm(c, w["wv_b"], quant).reshape(n, s, h, vd)
    q = jnp.concatenate([q[..., :hd], rope(cfg, q[..., hd:], pos)], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (n, s, h, r))], axis=-1)
    o = causal_attention(q, k, v, softmax_scale(cfg))
    return _mm(o.reshape(n, s, h * vd), w["wo"], quant)


def swiglu(y, wg, wu, wd, quant):
    return _mm(jax.nn.silu(_mm(y, wg, quant)) * _mm(y, wu, quant), wd, quant)


def router(cfg: dict, w: dict, y):
    """(weights (n, S, E), zero where not chosen; margin (n, S)) of normed
    inputs y, in float32 as published. The margin is the smaller of the
    gaps, in router logits, between the k-th expert chosen and the best
    left, and between the last group kept and the best group left."""
    e, k, g, tg = cfg["n_experts"], cfg["top_k"], cfg.get("n_group", 1), cfg.get("topk_group", 1)
    logits = jnp.einsum("nsd,de->nse", y, w["router"])
    scores = jax.nn.softmax(logits, axis=-1)
    grouped = scores.reshape(*scores.shape[:-1], g, e // g)
    group_score = grouped.max(axis=-1)                        # (n, S, g)
    gs = jnp.sort(group_score, axis=-1)[..., ::-1]
    kept = group_score >= gs[..., tg - 1:tg]
    masked = jnp.where(kept[..., None], grouped, 0.0).reshape(scores.shape)
    top = jnp.sort(masked, axis=-1)[..., ::-1]
    chosen = masked >= top[..., k - 1:k]
    weights = jnp.where(chosen, scores, 0.0)
    if cfg.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    else:
        weights = weights * cfg.get("routed_scaling_factor", 1.0)
    inf = jnp.full(scores.shape[:-1], jnp.inf)
    m_exp = (jnp.log(top[..., k - 1]) - jnp.log(top[..., k])
             if k < (e // g) * tg else inf)
    m_grp = jnp.log(gs[..., tg - 1]) - jnp.log(gs[..., tg]) if tg < g else inf
    return weights, jnp.minimum(m_exp, m_grp)


def moe(cfg: dict, w: dict, y, quant: str):
    """(routed experts held here + shared experts, router margin)."""
    first, count = W.held(cfg)
    weights, margin = router(cfg, w, y)
    mine = jnp.moveaxis(weights[..., first:first + count], -1, 0)  # (held, n, S)

    def one(acc, args):
        wt, wg, wu, wd = args
        return acc + wt[..., None] * swiglu(y, wg, wu, wd, quant), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(y),
                             (mine, w["w_gate"], w["w_up"], w["w_down"]))
    shared = swiglu(y, w["shared_gate"], w["shared_up"], w["shared_down"], quant)
    return routed + shared, margin


def layer_forward(cfg: dict, w: dict, x, quant: str = "none"):
    """One decoder layer over whole sequences x (n, S, d), float32: (x,
    router margin (n, S), inf in a dense layer)."""
    w = {k: a.astype(jnp.float32) for k, a in w.items()}
    eps = cfg["norm_eps"]
    x = x + attention(cfg, w, rmsnorm(x, w["attn_norm"], eps), quant)
    y = rmsnorm(x, w["ffn_norm"], eps)
    if "router" not in w:
        return (x + swiglu(y, w["w_gate"], w["w_up"], w["w_down"], quant),
                jnp.full(x.shape[:2], jnp.inf))
    out, margin = moe(cfg, w, y, quant)
    return x + out, margin


@functools.lru_cache(maxsize=None)
def _programs(frozen: tuple, quant: str):
    cfg = dict(frozen)
    layer = jax.jit(lambda w, x: layer_forward(cfg, w, x, quant), donate_argnums=1)
    embed = jax.jit(lambda top, t: top["embedding"].astype(jnp.float32)[t])
    final = jax.jit(lambda top, x: rmsnorm(x, top["final_norm"].astype(jnp.float32),
                                           cfg["norm_eps"]))
    return layer, embed, final


def hidden(cfg: dict, seed: int, tokens: np.ndarray, quant: str = "none"):
    """(final-norm hidden states (n, S, d), smallest router margin over the
    layers (n, S)) of token rows (n, S), layer by layer, with weights
    regenerated from the seed."""
    with jax.default_matmul_precision("highest"):
        layer, embed, final = _programs(_frozen(cfg), quant)
        top = W.top(cfg, seed)
        x = embed(top, jnp.asarray(tokens, jnp.int32))
        margin = jnp.full(tokens.shape, jnp.inf)
        for i in range(cfg["n_layers"]):
            x, m = layer(W.layer(cfg, seed, i), x)
            margin = jnp.minimum(margin, m)
        return final(top, x), margin


@functools.partial(jax.jit, static_argnames=("quant",))
def _row_block(wt, hb, hcb, served, quant):
    ref = jnp.einsum("rd,dv->rv", hb, wt)
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    if hcb is None:
        return best, got, got
    pick = jnp.argmax(_mm(hcb, wt, quant), axis=-1)
    return best, got, jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]


def served_gaps(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
                served: np.ndarray, control: bool = False) -> dict:
    """Logit gaps of served tokens under the reference, as
    ``dense_gqa.served_gaps`` (``gap``, with ``control`` also
    ``control_gap``), and ``margin`` (n, G): the smallest router margin,
    over the layers, of each position whose logits chose a served token."""
    n, g = rows.shape
    h, margin = hidden(cfg, seed, tokens)
    hc = hidden(cfg, seed, tokens, quant="fp8")[0] if control else None
    with jax.default_matmul_precision("highest"):
        wt = W.top(cfg, seed)["lm_head"].astype(jnp.float32)
        pad = -(n * g) % ROW_BLOCK
        flat = (lambda a: jnp.pad(a.reshape(n * g, -1), ((0, pad), (0, 0))))
        hr = flat(_take_rows(h, jnp.asarray(rows)))
        hcr = flat(_take_rows(hc, jnp.asarray(rows))) if control else None
        sv = jnp.asarray(np.pad(served.reshape(-1), (0, pad)), jnp.int32)
        outs = []
        for a in range(0, n * g + pad, ROW_BLOCK):
            sl = slice(a, a + ROW_BLOCK)
            outs.append([np.asarray(o, np.float64) for o in _row_block(
                wt, hr[sl], None if hcr is None else hcr[sl], sv[sl],
                quant="fp8")])
    best, got, ctl = (np.concatenate(c)[:n * g].reshape(n, g) for c in zip(*outs))
    out = {"gap": best - got,
           "margin": np.take_along_axis(np.asarray(margin, np.float64), rows, axis=1)}
    if control:
        out["control_gap"] = best - ctl
    return out
