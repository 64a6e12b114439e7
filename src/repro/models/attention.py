"""Attention: GQA and MLA (DeepSeek-V2), with three SDPA implementations.

* ``naive``   — materializes scores; tiny shapes / oracles only.
* ``chunked`` — flash-style online-softmax over KV blocks expressed in pure
  jnp ``lax.scan`` (O(block) memory, compiles at 32k+ without materializing
  S). This is the default compile path on CPU and the reference the Pallas
  kernel is validated against. Each block step is ``jax.checkpoint``-ed so
  the backward pass recomputes block scores (flash-backward behaviour).
* ``pallas``  — the TPU kernel in ``repro.kernels`` (selected via MSM policy
  on real hardware).

Decode paths take a KV cache (or MLA latent cache) and a scalar position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.base import P, Specs
from repro.models.layers import apply_rope, rmsnorm, yarn_frequencies, yarn_mscale

NEG_INF = -1e30


# --------------------------------------------------------------------------------
# SDPA implementations (q: B,Sq,H,D; k/v: B,Skv,KVH,D)
# --------------------------------------------------------------------------------

def naive_attention(q, k, v, *, causal: bool, q_offset=0, kv_len=None,
                    scale: float | None = None):
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, sq, kvh, g, d)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    idx_q = jnp.arange(sq) + q_offset
    idx_k = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= idx_k[None, :] <= idx_q[:, None]
    if kv_len is not None:
        mask &= idx_k[None, :] < kv_len
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(b, sq, h, v.shape[-1])


def chunked_attention(q, k, v, *, causal: bool, q_chunk: int = 512,
                      kv_chunk: int = 1024, scale: float | None = None):
    """Flash-style attention in pure jnp: scan over q chunks; inner scan over
    kv chunks with online softmax. Memory is O(q_chunk x kv_chunk)."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    dv = v.shape[-1]
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    pad_q = (-sq) % q_chunk
    pad_kv = (-skv) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // kv_chunk
    qc = q.reshape(b, nq, q_chunk, kvh, g, d).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(b, nk, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, kv_chunk, kvh, d).transpose(1, 0, 2, 3, 4)
    valid_kv = skv

    @jax.checkpoint
    def kv_step(carry, inputs):
        m, l, acc, q_blk, q_start = carry
        k_blk, v_blk, ki = inputs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk).astype(jnp.float32)
        s = s * scale
        iq = jnp.arange(q_chunk)[:, None]
        ik = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
        mask = ik < valid_kv
        if causal:
            mask = mask & (ik <= (q_start + iq))
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new, q_blk, q_start), None

    def q_block(carry, inputs):
        qi, q_blk = inputs
        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, dv), jnp.float32)
        (m, l, acc, _, _), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0, q_blk, qi * q_chunk),
            (kc, vc, jnp.arange(nk)),
        )
        out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
        return carry, out

    _, results = jax.lax.scan(q_block, 0, (jnp.arange(nq), qc))
    # (nq, b, kvh, g, q_chunk, dv) -> (b, nq*q_chunk, h, dv)
    out = results.transpose(1, 0, 4, 2, 3, 5).reshape(b, nq * q_chunk, h, dv)
    return out[:, :sq]


# --------------------------------------------------------------------------------
# custom-VJP flash attention: O(block) memory in fwd AND bwd.
# The forward saves only (q, k, v, out, lse); the backward recomputes score
# blocks — the flash-attention-2 recipe (arXiv:2307.08691) expressed in jnp.
# This is the training default: autodiff-through-scan would stack per-step
# online-softmax carries (multi-GiB per layer at 4k+ sequence lengths).
# --------------------------------------------------------------------------------

def _blockify(x, n, c):
    """(B,S,...) -> (n, B, c, ...)"""
    b = x.shape[0]
    return x.reshape(b, n, c, *x.shape[2:]).swapaxes(0, 1)


def _flash_fwd_impl(q, k, v, pos_q, pos_k, causal, scale, q_chunk, kv_chunk):
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    dv = v.shape[-1]
    g = h // kvh
    nq, nk = sq // q_chunk, skv // kv_chunk
    qc = _blockify(q.reshape(b, sq, kvh, g, d), nq, q_chunk)
    kc = _blockify(k, nk, kv_chunk)
    vc = _blockify(v, nk, kv_chunk)
    pqc = _blockify(pos_q, nq, q_chunk)     # (nq, B, qc)
    pkc = _blockify(pos_k, nk, kv_chunk)

    def q_block(_, inputs):
        qi, q_blk, pq_blk = inputs
        m0 = jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, q_chunk, dv), jnp.float32)

        def kv_step(carry, kv_inputs):
            m, l, acc = carry
            k_blk, v_blk, pk_blk, ki = kv_inputs
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk).astype(jnp.float32) * scale
            if causal:
                # runtime positions (supports packing; also keeps XLA from
                # constant-folding full-score-shaped masks)
                msk = pk_blk[:, None, :] <= pq_blk[:, :, None]   # (B,qc,kc)
                s = jnp.where(msk[:, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kc, vc, pkc, jnp.arange(nk)))
        out = (acc / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return None, (out, lse)

    _, (outs, lses) = jax.lax.scan(q_block, None, (jnp.arange(nq), qc, pqc))
    # outs: (nq,B,kvh,g,qc,dv); lses: (nq,B,kvh,g,qc)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dv)
    lse = lses.transpose(1, 0, 4, 2, 3).reshape(b, sq, h)
    return out, lse


def _flash_bwd_impl(q, k, v, pos_q, pos_k, out, lse, dout, causal, scale,
                    q_chunk, kv_chunk):
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    dv_dim = v.shape[-1]
    g = h // kvh
    nq, nk = sq // q_chunk, skv // kv_chunk
    qg = q.reshape(b, sq, kvh, g, d)
    og = out.reshape(b, sq, kvh, g, dv_dim)
    dog = dout.reshape(b, sq, kvh, g, dv_dim)
    lseg = lse.reshape(b, sq, kvh, g)
    delta = jnp.sum(og.astype(jnp.float32) * dog.astype(jnp.float32), -1)
    qc = _blockify(qg, nq, q_chunk)
    doc = _blockify(dog, nq, q_chunk)
    lsec = _blockify(lseg, nq, q_chunk)
    dc = _blockify(delta, nq, q_chunk)
    kc = _blockify(k, nk, kv_chunk)
    vc = _blockify(v, nk, kv_chunk)
    pqc = _blockify(pos_q, nq, q_chunk)
    pkc = _blockify(pos_k, nk, kv_chunk)

    def p_block(pq_blk, pk_blk, q_blk, k_blk, lse_blk):
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk).astype(jnp.float32) * scale
        if causal:
            msk = pk_blk[:, None, :] <= pq_blk[:, :, None]
            s = jnp.where(msk[:, None, None], s, NEG_INF)
        # lse_blk: (B,qc,kvh,g) -> (B,kvh,g,qc)
        lse_t = lse_blk.transpose(0, 2, 3, 1)
        return jnp.exp(s - lse_t[..., None])

    # ---- dq: scan q blocks, inner scan kv ----
    def dq_block(_, inputs):
        pq_blk, q_blk, do_blk, lse_blk, d_blk = inputs
        do_t = do_blk.transpose(0, 2, 3, 1, 4).astype(jnp.float32)
        d_t = d_blk.transpose(0, 2, 3, 1)

        def kv_step(acc, kv_inputs):
            k_blk, v_blk, pk_blk = kv_inputs
            p = p_block(pq_blk, pk_blk, q_blk, k_blk, lse_blk)
            dp = jnp.einsum("bhgqe,bkhe->bhgqk", do_t,
                            v_blk.astype(jnp.float32))
            ds = p * (dp - d_t[..., None]) * scale
            return acc + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                    k_blk.astype(jnp.float32)), None

        acc0 = jnp.zeros((b, q_chunk, kvh, g, d), jnp.float32)
        dq_blk, _ = jax.lax.scan(kv_step, acc0, (kc, vc, pkc))
        return None, dq_blk

    _, dq_blocks = jax.lax.scan(dq_block, None,
                                (pqc, qc, doc, lsec, dc))
    dq = dq_blocks.swapaxes(0, 1).reshape(b, sq, h, d).astype(q.dtype)

    # ---- dk, dv: scan kv blocks, inner scan q ----
    def dkv_block(_, inputs):
        pk_blk, k_blk, v_blk = inputs

        def q_step(carry, q_inputs):
            dk_acc, dv_acc = carry
            pq_blk, q_blk, do_blk, lse_blk, d_blk = q_inputs
            p = p_block(pq_blk, pk_blk, q_blk, k_blk, lse_blk)
            do_t = do_blk.transpose(0, 2, 3, 1, 4).astype(jnp.float32)
            d_t = d_blk.transpose(0, 2, 3, 1)
            dv_acc = dv_acc + jnp.einsum("bhgqk,bhgqe->bkhe", p, do_t)
            dp = jnp.einsum("bhgqe,bkhe->bhgqk", do_t, v_blk.astype(jnp.float32))
            ds = p * (dp - d_t[..., None]) * scale
            dk_acc = dk_acc + jnp.einsum("bhgqk,bqhgd->bkhd", ds,
                                         q_blk.astype(jnp.float32))
            return (dk_acc, dv_acc), None

        dk0 = jnp.zeros((b, kv_chunk, kvh, d), jnp.float32)
        dv0 = jnp.zeros((b, kv_chunk, kvh, dv_dim), jnp.float32)
        (dk_blk, dv_blk), _ = jax.lax.scan(
            q_step, (dk0, dv0), (pqc, qc, doc, lsec, dc))
        return None, (dk_blk, dv_blk)

    _, (dk_blocks, dv_blocks) = jax.lax.scan(dkv_block, None,
                                             (pkc, kc, vc))
    dk = dk_blocks.swapaxes(0, 1).reshape(b, skv, kvh, d).astype(k.dtype)
    dv = dv_blocks.swapaxes(0, 1).reshape(b, skv, kvh, dv_dim).astype(v.dtype)
    return dq, dk, dv


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention_jnp(q, k, v, pos_q, pos_k, causal, scale, q_chunk,
                        kv_chunk):
    out, _ = _flash_fwd_impl(q, k, v, pos_q, pos_k, causal, scale, q_chunk,
                             kv_chunk)
    return out


def _flash_vjp_fwd(q, k, v, pos_q, pos_k, causal, scale, q_chunk, kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, pos_q, pos_k, causal, scale, q_chunk,
                               kv_chunk)
    return out, (q, k, v, pos_q, pos_k, out, lse)


def _flash_vjp_bwd(causal, scale, q_chunk, kv_chunk, saved, dout):
    q, k, v, pos_q, pos_k, out, lse = saved
    dq, dk, dv = _flash_bwd_impl(q, k, v, pos_q, pos_k, out, lse, dout,
                                 causal, scale, q_chunk, kv_chunk)
    return dq, dk, dv, jnp.zeros_like(pos_q), jnp.zeros_like(pos_k)


flash_attention_jnp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool, scale: float | None = None,
                    q_chunk: int = 512, kv_chunk: int = 1024,
                    positions=None, kv_positions=None):
    """Shape-normalizing wrapper: pads S to chunk multiples, handles dv != d.
    ``positions``/``kv_positions``: (B,S) int32 runtime positions (sequence
    packing; also prevents the mask from being constant-folded at score
    shape)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else d ** -0.5
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(sq, dtype=jnp.int32), (b, sq))
    if kv_positions is None:
        kv_positions = (positions if sq == skv else jnp.broadcast_to(
            jnp.arange(skv, dtype=jnp.int32), (b, skv)))
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    pad_q = (-sq) % q_chunk
    pad_kv = (-skv) % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, pad_q)))
    if pad_kv:
        if causal and sq == skv + pad_kv - pad_q:
            k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
            # padded keys get position INT32_MAX -> masked for every query
            kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad_kv)),
                                   constant_values=jnp.iinfo(jnp.int32).max)
        else:
            kv_chunk = next(c for c in range(kv_chunk, 0, -1) if skv % c == 0)
    out = flash_attention_jnp(q, k, v, positions, kv_positions, causal, scale,
                              q_chunk, kv_chunk)
    return out[:, :sq]


def decode_attention(q, k_cache, v_cache, kv_len, scale: float | None = None,
                     k_new=None, v_new=None):
    """Single-token attention against a (possibly sequence-sharded) cache.

    q: (B,1,H,D); caches: (B,KVH,S,D), head-major; kv_len: number of valid
    entries. ``k_new``/``v_new`` (B,KVH,1,D), where given, are the token's
    own key and value, kept out of the cache: the token attends to the
    cache's first ``kv_len`` positions and to its own key and value under
    one softmax, so the cache is only read. Score/softmax reductions over
    the cache axis lower to psum-style collectives when S is sharded
    (context-parallel flash-decode).
    """
    b, _, h, d = q.shape
    _, kvh, s, _ = k_cache.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qg = q.reshape(b, kvh, g, d)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache).astype(jnp.float32)
    scores = scores * scale
    mask = jnp.arange(s)[None, None, None, :] < kv_len
    scores = jnp.where(mask, scores, NEG_INF)
    # int8-quantized caches: compute the weighted sum in bf16 (dequant is a
    # scale-fold upstream; the cast here keeps softmax weights non-integer)
    acc_dtype = jnp.bfloat16 if v_cache.dtype == jnp.int8 else v_cache.dtype
    if k_new is None:
        p = jax.nn.softmax(scores, axis=-1).astype(acc_dtype)
        out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(acc_dtype))
        return out.reshape(b, 1, h, v_cache.shape[-1])
    # two blocks of one online softmax: the cache's scores and the token's
    # own, under a shared max and one denominator
    s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_new[:, :, 0]).astype(jnp.float32)
    s_new = s_new * scale
    m = jnp.maximum(scores.max(axis=-1), s_new)
    e = jnp.exp(scores - m[..., None])
    e_new = jnp.exp(s_new - m)
    denom = e.sum(axis=-1) + e_new
    out = jnp.einsum("bhgk,bhkd->bhgd", (e / denom[..., None]).astype(acc_dtype),
                     v_cache.astype(acc_dtype),
                     preferred_element_type=jnp.float32)
    out = out + (e_new / denom)[..., None] * v_new.astype(jnp.float32)
    return out.astype(acc_dtype).reshape(b, 1, h, v_cache.shape[-1])


def sdpa(q, k, v, *, causal: bool, impl: str = "chunked",
         q_chunk: int = 512, kv_chunk: int = 1024, scale=None,
         positions=None):
    if impl == "naive" or q.shape[1] <= 256:
        return naive_attention(q, k, v, causal=causal, scale=scale)
    if impl == "pallas":
        from repro.kernels import ops as kops

        return kops.flash_attention_op(q, k, v, causal=causal, scale=scale)
    return flash_attention(q, k, v, causal=causal, q_chunk=q_chunk,
                           kv_chunk=kv_chunk, scale=scale,
                           positions=positions)


# --------------------------------------------------------------------------------
# GQA attention module
# --------------------------------------------------------------------------------

def gqa_specs(cfg: ModelConfig) -> Specs:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": P((d, h * hd), ("embed", "heads")),
        "wk": P((d, kvh * hd), ("embed", "kv_heads")),
        "wv": P((d, kvh * hd), ("embed", "kv_heads")),
        "wo": P((h * hd, d), ("heads", "embed")),
    }


def gqa_project_qkv(params, cfg: ModelConfig, x, positions):
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(b, s, h, hd)
    k = jnp.einsum("bsd,de->bse", x, params["wk"]).reshape(b, s, kvh, hd)
    v = jnp.einsum("bsd,de->bse", x, params["wv"]).reshape(b, s, kvh, hd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attention(params, cfg: ModelConfig, x, positions, *, causal=True,
                  impl="chunked"):
    q, k, v = gqa_project_qkv(params, cfg, x, positions)
    from repro.sharding.optflags import opt
    from repro.sharding.partition import constrain

    if opt("gqa_expand_kv") and cfg.n_kv_heads < cfg.n_heads:
        g = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    if opt("attn_gather_once"):
        # settle the attention layout once, outside the block scans
        q = constrain(q, ("pod", "data"), None, "model", None)
        k = constrain(k, ("pod", "data"), None, "model", None)
        v = constrain(v, ("pod", "data"), None, "model", None)
    out = sdpa(q, k, v, causal=causal, impl=impl, positions=positions)
    b, s = x.shape[:2]
    return jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), params["wo"])


def gqa_decode(params, cfg: ModelConfig, x, cache_k, cache_v, pos):
    """One-token decode against one layer's cache, which it reads where it
    lies and never writes. cache_[kv]: (B, KVH, S, D); pos: scalar index of
    the new token, which attends to the cache's positions before ``pos`` and
    to its own key and value. Returns (out, k_row, v_row): the new token's
    rows (B, KVH, 1, D) in the cache's dtype, for the caller to write at
    ``pos`` under its ``cache_update`` scope."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q, k, v = gqa_project_qkv(params, cfg, x, positions)
    k_row = jnp.swapaxes(k, 1, 2).astype(cache_k.dtype)
    v_row = jnp.swapaxes(v, 1, 2).astype(cache_v.dtype)
    out = decode_attention(q, cache_k, cache_v, kv_len=pos,
                           k_new=k_row, v_new=v_row)
    out = jnp.einsum("bse,ed->bsd", out.reshape(b, 1, -1), params["wo"])
    return out, k_row, v_row


# --------------------------------------------------------------------------------
# MLA attention (DeepSeek-V2): latent-compressed KV cache
# --------------------------------------------------------------------------------

def mla_specs(cfg: ModelConfig) -> Specs:
    d, h = cfg.d_model, cfg.n_heads
    hd, r, vd = cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    ql, kvl = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "wq_a": P((d, ql), ("embed", "lora")),
        "wq_a_norm": {"scale": P((ql,), ("lora",), init="ones")},
        "wq_b": P((ql, h * (hd + r)), ("lora", "heads")),
        "wkv_a": P((d, kvl + r), ("embed", "lora")),
        "wkv_a_norm": {"scale": P((kvl,), ("lora",), init="ones")},
        "wk_b": P((kvl, h * hd), ("lora", "heads")),
        "wv_b": P((kvl, h * vd), ("lora", "heads")),
        "wo": P((h * vd, d), ("heads", "embed")),
    }


def mla_rope(cfg: ModelConfig, x, positions):
    """Rope on MLA's rope dims x (..., S, H, r), YaRN-scaled where the
    configuration says. The published model rotates interleaved pairs
    (2i, 2i+1) and returns them de-interleaved; so does this: the dims are
    de-interleaved first, then rotated as halves."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    if cfg.yarn_factor <= 1.0:
        return apply_rope(x, positions, cfg.rope_theta)
    freqs = yarn_frequencies(x.shape[-1], cfg.rope_theta, cfg.yarn_factor,
                             cfg.yarn_original_max_position,
                             cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    out = apply_rope(x, positions, cfg.rope_theta, freqs)
    m = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
         / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return out if m == 1.0 else (out * m).astype(out.dtype)


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(qk_nope + qk_rope) ** -0.5, times YaRN's mscale(factor,
    mscale_all_dim) squared where the rope is YaRN-scaled."""
    scale = (cfg.head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.yarn_factor > 1.0 and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_query(params, cfg: ModelConfig, x, positions):
    """(q_nope (B,S,H,hd), q_rope (B,S,H,r)): the query through its
    normalised latent."""
    b, s, _ = x.shape
    h, hd, r = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    q = rmsnorm(params["wq_a_norm"], jnp.einsum("bsd,dl->bsl", x, params["wq_a"]),
                cfg.norm_eps)
    q = jnp.einsum("bsl,le->bse", q, params["wq_b"]).reshape(b, s, h, hd + r)
    return q[..., :hd], mla_rope(cfg, q[..., hd:], positions)


def _mla_latent(params, cfg: ModelConfig, x, positions):
    """(c_kv (B,S,kvl), k_rope (B,S,r)): what the cache holds of each
    token, the normalised KV latent and the shared rope key."""
    kvl = cfg.kv_lora_rank
    ckv = jnp.einsum("bsd,dl->bsl", x, params["wkv_a"])
    c_kv = rmsnorm(params["wkv_a_norm"], ckv[..., :kvl], cfg.norm_eps)
    k_rope = mla_rope(cfg, ckv[:, :, None, kvl:], positions)[:, :, 0]
    return c_kv, k_rope


def mla_attention(params, cfg: ModelConfig, x, positions, *, causal=True,
                  impl="chunked"):
    """Whole-sequence MLA in the expanded form: per-head keys and values
    rebuilt from the latent."""
    b, s, _ = x.shape
    h, hd, r, vd = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = _mla_query(params, cfg, x, positions)
    c_kv, k_rope = _mla_latent(params, cfg, x, positions)
    k_nope = jnp.einsum("bsl,le->bse", c_kv, params["wk_b"]).reshape(b, s, h, hd)
    v = jnp.einsum("bsl,le->bse", c_kv, params["wv_b"]).reshape(b, s, h, vd)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, r))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = sdpa(q, k, v, causal=causal, impl=impl, scale=mla_softmax_scale(cfg),
               positions=positions)
    return jnp.einsum("bse,ed->bsd", out.reshape(b, s, -1), params["wo"])


def mla_decode(params, cfg: ModelConfig, x, cache_ckv, cache_krope, pos):
    """One-token MLA decode in the ABSORBED form, against one layer's latent
    cache, which it reads where it lies and never writes: wk_b is folded
    into the query and wv_b applied after the weighted latent sum, so
    per-head keys and values are never expanded over the cache. The cache
    holds only (kv_lora + rope) per token, a DRAM-traffic filter exactly as
    the paper's L3 argument has it.

    cache_ckv (B, S, kvl), cache_krope (B, S, r); pos: scalar index of the
    new token, which attends to the cache's positions before ``pos`` and to
    its own latent under one softmax. The contractions against the cache
    take its operands as they lie (bfloat16) and accumulate in float32.
    Returns (out, c_row, k_row): the token's rows (B, 1, kvl) and (B, 1, r)
    in the cache's dtype, for the caller to write at ``pos``."""
    b = x.shape[0]
    h, vd, kvl = cfg.n_heads, cfg.v_head_dim, cfg.kv_lora_rank
    dt = cache_ckv.dtype
    positions = jnp.full((b, 1), pos, jnp.int32)
    c_new, k_new = _mla_latent(params, cfg, x, positions)
    c_row, k_row = c_new.astype(dt), k_new.astype(dt)
    q_nope, q_rope = _mla_query(params, cfg, x, positions)
    wk_b = params["wk_b"].reshape(kvl, h, cfg.head_dim)
    q_abs = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0], wk_b).astype(dt)
    q_rope = q_rope[:, 0].astype(dt)
    scale = mla_softmax_scale(cfg)
    f32 = jnp.float32
    with jax.named_scope("latent_core"):
        scores = (jnp.einsum("bhl,bkl->bhk", q_abs, cache_ckv,
                             preferred_element_type=f32)
                  + jnp.einsum("bhr,bkr->bhk", q_rope, cache_krope,
                               preferred_element_type=f32)) * scale
        mask = jnp.arange(cache_ckv.shape[1])[None, None, :] < pos
        scores = jnp.where(mask, scores, NEG_INF)
        s_new = (jnp.einsum("bhl,bl->bh", q_abs, c_row[:, 0],
                            preferred_element_type=f32)
                 + jnp.einsum("bhr,br->bh", q_rope, k_row[:, 0],
                              preferred_element_type=f32)) * scale
        # two blocks of one softmax: the cache's positions and the token's own
        m = jnp.maximum(scores.max(axis=-1), s_new)
        e = jnp.exp(scores - m[..., None])
        e_new = jnp.exp(s_new - m)
        denom = e.sum(axis=-1) + e_new
        ctx = jnp.einsum("bhk,bkl->bhl", (e / denom[..., None]).astype(dt),
                         cache_ckv, preferred_element_type=f32)
        ctx = ctx + (e_new / denom)[..., None] * c_row.astype(f32)
    wv_b = params["wv_b"].reshape(kvl, h, vd)
    out = jnp.einsum("bhl,lhv->bhv", ctx.astype(x.dtype), wv_b)
    out = jnp.einsum("be,ed->bd", out.reshape(b, h * vd), params["wo"])
    return out[:, None], c_row, k_row
