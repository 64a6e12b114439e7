"""The decode step reads the KV cache where it lies and writes only the new
token's rows.

The oracle is the formulation it replaced, written out here: write the new
token's key and value into the cache at ``pos``, then attend over the whole
cache with the positions after ``pos`` masked.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import LanguageModel
from repro.models.attention import NEG_INF, decode_attention, gqa_project_qkv
from repro.models.layers import embed, ffn, logits_for_tokens, rmsnorm

MAX_LEN = 24


def _write_then_attend(q, k_cache, v_cache, k_new, v_new, pos):
    """Returns (out, k_cache, v_cache) with the new rows written at ``pos``.
    Caches (B, KVH, S, D), rows (B, KVH, 1, D)."""
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new, (0, 0, pos, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new, (0, 0, pos, 0))
    b, _, h, d = q.shape
    kvh, s = k_cache.shape[1:3]
    qg = q.reshape(b, kvh, h // kvh, d)
    scores = jnp.einsum("bhgd,bhkd->bhgk", qg, k_cache).astype(jnp.float32)
    scores = jnp.where(jnp.arange(s) <= pos, scores * d ** -0.5, NEG_INF)
    acc = jnp.bfloat16 if v_cache.dtype == jnp.int8 else v_cache.dtype
    p = jax.nn.softmax(scores, axis=-1).astype(acc)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(acc))
    return out.reshape(b, 1, h, d), k_cache, v_cache


def _kv(key, shape, dtype):
    if dtype == jnp.int8:
        return jax.random.randint(key, shape, -4, 5).astype(jnp.int8)
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# What the float32 weighted sum leaves after its one rounding to the output's
# dtype, set from the dtype's resolution: the oracle rounds every weight to
# bf16 and sums in bf16; the new path rounds the output once.
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2 ** -6, jnp.int8: 2 ** -6}


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8],
                         ids=["f32", "bf16", "int8"])
@pytest.mark.parametrize("pos", [0, 1, MAX_LEN // 2, MAX_LEN - 1])
def test_attention_with_new_rows_matches_write_then_attend(pos, dtype,
                                                           head_dim):
    b, h, kvh, s = 2, 8, 2, MAX_LEN
    ks = jax.random.split(jax.random.PRNGKey(pos), 5)
    q_dtype = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
    q = jax.random.normal(ks[0], (b, 1, h, head_dim), jnp.float32).astype(q_dtype)
    # every cache position holds data, those at and after pos too: the mask,
    # not the zeros of a fresh cache, must keep them out
    k_cache = _kv(ks[1], (b, kvh, s, head_dim), dtype)
    v_cache = _kv(ks[2], (b, kvh, s, head_dim), dtype)
    k_new = _kv(ks[3], (b, kvh, 1, head_dim), dtype)
    v_new = _kv(ks[4], (b, kvh, 1, head_dim), dtype)
    if dtype == jnp.int8:
        q = q / 4   # int8 keys are whole numbers: keep the softmax from saturating
    got = decode_attention(q, k_cache, v_cache, kv_len=pos,
                           k_new=k_new, v_new=v_new)
    want, _, _ = _write_then_attend(q, k_cache, v_cache, k_new, v_new, pos)
    assert got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=TOL[dtype], atol=TOL[dtype])


def _write_then_attend_step(model, params, cache, tokens, pos):
    """The dense decode step as it was: each layer writes its rows into its
    cache slice, then attends over the whole slice."""
    cfg = model.cfg
    x = embed(params["emb"], tokens)
    positions = jnp.full((x.shape[0], 1), pos, jnp.int32)
    ks, vs = [], []
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[layer], params["layers"])
        h = rmsnorm(p["ln1"], x, cfg.norm_eps)
        q, k, v = gqa_project_qkv(p["attn"], cfg, h, positions)
        k_l, v_l = cache["k"][layer], cache["v"][layer]
        o, k_l, v_l = _write_then_attend(q, k_l, v_l,
                                         k.swapaxes(1, 2).astype(k_l.dtype),
                                         v.swapaxes(1, 2).astype(v_l.dtype), pos)
        x = x + jnp.einsum("bse,ed->bsd", o.reshape(*x.shape[:2], -1),
                           p["attn"]["wo"])
        x = x + ffn(p["ffn"], rmsnorm(p["ln2"], x, cfg.norm_eps))
        ks.append(k_l)
        vs.append(v_l)
    h = rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return logits_for_tokens(params["emb"], h), {"k": jnp.stack(ks),
                                                 "v": jnp.stack(vs)}


def _filled_cache(model, batch, dtype, enc_len=0):
    """A cache whose every entry holds data, so that a write shows."""
    cache = model.init_cache(batch, MAX_LEN, dtype=dtype, enc_len=enc_len)
    keys = jax.random.split(jax.random.PRNGKey(7), len(cache))
    return {n: jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
            for k, (n, a) in zip(keys, sorted(cache.items()))}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_decode_step_matches_write_then_attend(dtype):
    cfg = C.get("granite-3-2b").smoke()
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0), dtype=dtype)
    cache = _filled_cache(model, 2, dtype)
    tokens = jnp.array([[3], [11]], jnp.int32)
    pos = 5
    logits, new = jax.jit(model.decode_step)(params, cache, tokens,
                                             jnp.int32(pos))
    want_logits, want = _write_then_attend_step(model, params, cache, tokens,
                                                pos)
    for n in ("k", "v"):
        got, old = np.asarray(new[n], np.float32), np.asarray(cache[n], np.float32)
        # every position but pos untouched, bit for bit
        np.testing.assert_array_equal(np.delete(got, pos, axis=3),
                                      np.delete(old, pos, axis=3))
        _assert_close(got[:, :, :, pos], want[n][:, :, :, pos], TOL[dtype])
    _assert_close(logits, want_logits, TOL[dtype])


def _assert_close(got, want, tol):
    """Within ``tol`` of the largest magnitude: the rows and logits are
    projections of a hidden state whose rounding differs by ``tol`` of its
    scale, so their error scales with the vector, not with each element."""
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0,
                               atol=tol * np.abs(want).max())


@pytest.mark.parametrize("arch", ["zamba2-1.2b", "whisper-base"])
def test_decode_step_writes_only_pos(arch):
    """The hybrid's shared attention and the audio decoder write every KV
    row they hold at pos, and nothing else; the audio cross caches are read
    only."""
    cfg = C.get(arch).smoke()
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = _filled_cache(model, 2, jnp.bfloat16, enc_len=8)
    pos = 3
    _, new = jax.jit(model.decode_step)(params, cache, jnp.ones((2, 1), jnp.int32),
                                        jnp.int32(pos))
    written = ("shared_k", "shared_v") if cfg.family == "hybrid" else ("k", "v")
    for n in written:
        got, old = np.asarray(new[n], np.float32), np.asarray(cache[n], np.float32)
        np.testing.assert_array_equal(np.delete(got, pos, axis=3),
                                      np.delete(old, pos, axis=3))
        assert (got[:, :, :, pos] != old[:, :, :, pos]).any(axis=(1, 2, 3)).all()
    for n in set(cache) - set(written) - {"conv", "ssm"}:
        np.testing.assert_array_equal(np.asarray(new[n], np.float32),
                                      np.asarray(cache[n], np.float32))
