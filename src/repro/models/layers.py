"""Shared layer primitives: RMSNorm, RoPE, SwiGLU FFN, embeddings, chunked
cross-entropy. Pure functions over param dicts (see ``models.base``)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.base import P, Specs


# --------------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------------

def rmsnorm_specs(d: int) -> Specs:
    return {"scale": P((d,), ("embed",), init="ones")}


def rmsnorm(params, x, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------------
# Rotary position embeddings
# --------------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1 * mscale * ln(factor) + 1
    (1 where the context is not stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float, factor: float, original_max: int,
                     beta_fast: float, beta_slow: float) -> jax.Array:
    """YaRN inverse frequencies (arXiv:2309.00071, as DeepSeek-V2 publishes
    them): dimensions that turn more than ``beta_fast`` times over the
    original context keep their frequency, those that turn fewer than
    ``beta_slow`` times are divided by ``factor``, and a linear ramp joins
    the two."""
    def turns_dim(turns):
        return (dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = rope_frequencies(dim, theta)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    keep = 1.0 - ramp
    return extra / factor * (1.0 - keep) + extra * keep


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs: jax.Array | None = None) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S). ``freqs``
    (D/2,) replaces the plain ``rope_frequencies`` (YaRN)."""
    half = x.shape[-1] // 2
    if freqs is None:
        freqs = rope_frequencies(x.shape[-1], theta)        # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., :, None, :]                  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# --------------------------------------------------------------------------------
# SwiGLU FFN
# --------------------------------------------------------------------------------

def ffn_specs(d: int, d_ff: int) -> Specs:
    return {
        "w_gate": P((d, d_ff), ("embed", "ff")),
        "w_up": P((d, d_ff), ("embed", "ff")),
        "w_down": P((d_ff, d), ("ff", "embed")),
    }


def ffn(params, x):
    g = jnp.einsum("...d,df->...f", x, params["w_gate"])
    u = jnp.einsum("...d,df->...f", x, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return jnp.einsum("...f,fd->...d", h, params["w_down"])


# --------------------------------------------------------------------------------
# Embedding + LM head
# --------------------------------------------------------------------------------

def embedding_specs(vocab: int, d: int, tied: bool) -> Specs:
    s: Specs = {"embedding": P((vocab, d), ("vocab", "embed"), init="small")}
    if not tied:
        s["lm_head"] = P((d, vocab), ("embed", "vocab"))
    return s


def embed(params, tokens):
    return jnp.take(params["embedding"], tokens, axis=0)


def unembed_weight(params):
    if "lm_head" in params:
        return params["lm_head"]
    return params["embedding"].T


def chunked_cross_entropy(params, h, labels, chunk: int = 512,
                          mask=None) -> jax.Array:
    """Vocab projection + softmax-xent without materializing full logits.

    h: (B, S, D); labels: (B, S). Scans over S in chunks; each chunk's
    logits are (B, chunk, V) and are rematerialized in the backward pass.
    """
    w = unembed_weight(params)
    b, s, d = h.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask_full = jnp.pad(
            jnp.ones((b, s), jnp.float32) if mask is None else mask,
            ((0, 0), (0, pad)),
        )
    else:
        mask_full = jnp.ones((b, s), jnp.float32) if mask is None else mask
    nc = h.shape[1] // chunk
    h = h.reshape(b, nc, chunk, d).swapaxes(0, 1)            # (nc, B, c, D)
    labels = labels.reshape(b, nc, chunk).swapaxes(0, 1)
    mask_full = mask_full.reshape(b, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        hx, lx, mx = xs
        logits = jnp.einsum("bcd,dv->bcv", hx, w).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        loss = ((lse - gold) * mx).sum()
        return carry + loss, None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (h, labels, mask_full))
    return total / jnp.maximum(mask_full.sum(), 1.0)


def logits_for_tokens(params, h):
    """Full logits (decode path: S is 1)."""
    return jnp.einsum("...d,dv->...v", h, unembed_weight(params))
