"""Plain float32 reference of a dense GQA decoder (Llama, Mistral, Granite).

Written from the published description, not from the program: token
embedding; per layer RMSNorm, rotary embedding (first half / second half
rotation, theta from the configuration), grouped-query causal attention
(query head i reads key/value head i // (H / KV)), softmax scaled by
head_dim ** -0.5, output projection, residual; RMSNorm, SwiGLU
(silu(x Wg) * (x Wu)) Wd, residual; final RMSNorm; logits against the head
(the embedding's transpose when tied). Granite's four published scalar
multipliers are left out, as the configuration file's ``departures`` say.

It runs under ``jax.default_matmul_precision("highest")`` and takes its
weights from ``weights.py`` one layer at a time, so that it fits beside
nothing else on one chip at the cells' sizes. Queries are attended in
blocks of ``Q_BLOCK`` rows and logits are formed in blocks of
``ROW_BLOCK`` positions.

``quant="fp8"`` is the control: every matrix product of a projection, the
FFN and the head takes float8_e4m3fn operands, scaled per output channel
(weights) and per token (activations), the step below bfloat16 that a
serving path could take.
"""
from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import weights as W  # noqa: E402

Q_BLOCK = 256
ROW_BLOCK = 256
FP8_MAX = 448.0


def _q8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n) in float32, or with fp8 operands."""
    if quant == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (..., S, heads, hd); rotate (first half, second half) pairs."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2.0 / hd))
    ang = positions[..., :, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v):
    """q (n, S, H, hd), k/v (n, S, KV, hd) -> (n, S, H, hd), causal."""
    n, s, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qb = min(Q_BLOCK, s)
    nb = -(-s // qb)
    q = jnp.pad(q, ((0, 0), (0, nb * qb - s), (0, 0), (0, 0)))
    qblocks = q.reshape(n, nb, qb, h, hd).swapaxes(0, 1)
    cols = jnp.arange(s)

    def block(args):
        i, qi = args
        scores = jnp.einsum("nqhd,nkhd->nhqk", qi, k) * (hd ** -0.5)
        rows = i * qb + jnp.arange(qb)
        scores = jnp.where(cols[None, :] <= rows[:, None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v)

    out = jax.lax.map(block, (jnp.arange(nb), qblocks))
    return out.swapaxes(0, 1).reshape(n, nb * qb, h, hd)[:, :s]


def layer_forward(cfg: dict, w: dict, x, quant: str = "none"):
    """One decoder layer over whole sequences x (n, S, d), float32."""
    n, s, _ = x.shape
    h, kvh, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    w = {k: a.astype(jnp.float32) for k, a in w.items()}
    pos = jnp.broadcast_to(jnp.arange(s), (n, s))
    y = rmsnorm(x, w["attn_norm"], cfg["norm_eps"])
    q = _mm(y, w["wq"], quant).reshape(n, s, h, hd)
    k = _mm(y, w["wk"], quant).reshape(n, s, kvh, hd)
    v = _mm(y, w["wv"], quant).reshape(n, s, kvh, hd)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    x = x + _mm(causal_attention(q, k, v).reshape(n, s, h * hd), w["wo"], quant)
    y = rmsnorm(x, w["ffn_norm"], cfg["norm_eps"])
    act = jax.nn.silu(_mm(y, w["w_gate"], quant)) * _mm(y, w["w_up"], quant)
    return x + _mm(act, w["w_down"], quant)


def head_weight(cfg: dict, top: dict):
    if cfg["tie_embeddings"]:
        return top["embedding"].astype(jnp.float32).T
    return top["lm_head"].astype(jnp.float32)


def final_hidden(cfg: dict, top: dict, x):
    return rmsnorm(x, top["final_norm"].astype(jnp.float32), cfg["norm_eps"])


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items() if not isinstance(v, (list, dict))))


@functools.lru_cache(maxsize=None)
def _programs(frozen: tuple, quant: str):
    cfg = dict(frozen)
    layer = jax.jit(lambda w, x: layer_forward(cfg, w, x, quant), donate_argnums=1)
    embed = jax.jit(lambda top, t: top["embedding"].astype(jnp.float32)[t])
    final = jax.jit(lambda top, x: final_hidden(cfg, top, x))
    return layer, embed, final


def hidden(cfg: dict, seed: int, tokens: np.ndarray, quant: str = "none"):
    """Final-norm hidden states (n, S, d) of token rows (n, S), made layer
    by layer with weights regenerated from the seed."""
    with jax.default_matmul_precision("highest"):
        layer, embed, final = _programs(_frozen(cfg), quant)
        top = W.top(cfg, seed)
        x = embed(top, jnp.asarray(tokens, jnp.int32))
        for i in range(cfg["n_layers"]):
            x = layer(W.layer(cfg, seed, i), x)
        return final(top, x)


@jax.jit
def _take_rows(a, rows):
    return jnp.take_along_axis(a, rows[:, :, None], axis=1)


@functools.partial(jax.jit, static_argnames=("quant",))
def _row_block(wt, hb, hcb, served, quant):
    ref = jnp.einsum("rd,dv->rv", hb, wt)
    best = ref.max(axis=-1)
    got = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    if hcb is None:
        return best, got, got
    ctl = _mm(hcb, wt, quant)
    pick = jnp.argmax(ctl, axis=-1)
    return best, got, jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]


def served_gaps(cfg: dict, seed: int, tokens: np.ndarray, rows: np.ndarray,
                served: np.ndarray, control: bool = False) -> dict:
    """Logit gaps of served tokens under the reference.

    tokens (n, S): prompt and served tokens, padded to a common S (causal
    attention makes the padding invisible to earlier positions). rows (n, G):
    the position whose logits chose each served token; served (n, G): the
    tokens the program served there. Returns float64 arrays (n, G):
    ``gap`` = reference best logit minus the served token's reference logit;
    with ``control``, also ``control_gap`` = the same for the token that the
    fp8 control puts first.
    """
    n, g = rows.shape
    h = hidden(cfg, seed, tokens)
    hc = hidden(cfg, seed, tokens, quant="fp8") if control else None
    with jax.default_matmul_precision("highest"):
        wt = head_weight(cfg, W.top(cfg, seed))
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
    out = {"gap": best - got}
    if control:
        out["control_gap"] = best - ctl
    return out


def loss(cfg: dict, top: dict, layers: list, tokens, labels):
    """Mean next-token cross-entropy of token rows (n, S) against labels,
    all weights given (float32 trees); for training checks at small sizes."""
    x = top["embedding"].astype(jnp.float32)[tokens]
    for w in layers:
        x = layer_forward(cfg, w, x)
    logits = final_hidden(cfg, top, x) @ head_weight(cfg, top)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
