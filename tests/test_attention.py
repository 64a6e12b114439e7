"""Flash attention (custom-vjp jnp path) vs naive oracle: values + grads,
hypothesis-driven shape sweeps; MLA equivalence; decode paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.models.attention import (decode_attention, flash_attention,
                                    naive_attention)


@settings(max_examples=12, deadline=None)
@given(
    b=st.integers(1, 2),
    s_pow=st.integers(4, 7),
    kvh=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([8, 16, 32]),
    causal=st.booleans(),
    qc=st.sampled_from([16, 48, 64]),
    kc=st.sampled_from([16, 32, 64]),
)
def test_flash_matches_naive_fwd(b, s_pow, kvh, g, d, causal, qc, kc):
    s = 2 ** s_pow
    h = kvh * g
    ks = jax.random.split(jax.random.PRNGKey(s + h + d), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, q_chunk=qc, kv_chunk=kc)
    want = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_naive(causal):
    b, s, h, kvh, d = 2, 128, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, q_chunk=32,
                                kv_chunk=64) ** 2).sum()

    def loss_naive(q, k, v):
        return (naive_attention(q, k, v, causal=causal) ** 2).sum()

    g1 = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=2e-4, rtol=2e-4)


def test_flash_packed_positions():
    """Packed sequences: two documents packed in one row must not attend
    across the boundary when positions restart (position-based masking)."""
    b, s, h, d = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)
    # positions restart at 32 — tokens 32.. have positions 0..31: with the
    # position-causal rule token 32 (pos 0) attends to every key with pos<=0:
    # i.e. keys 0 (pos 0) and 32 (pos 0). This matches the mask definition.
    pos = jnp.concatenate([jnp.arange(32), jnp.arange(32)])[None, :]
    got = flash_attention(q, k, v, causal=True, q_chunk=16, kv_chunk=16,
                          positions=pos, kv_positions=pos)
    # oracle: naive with explicit mask pos_k <= pos_q
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    mask = pos[0][None, :] <= pos[0][:, None]
    scores = jnp.where(mask[None, None], scores, -1e30)
    p = jax.nn.softmax(scores, -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_decode_attention_matches_naive():
    b, h, kvh, d, s = 2, 8, 2, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    kv_len = 40
    # the decode cache is head-major, (B, KVH, S, D)
    got = decode_attention(q, k.swapaxes(1, 2), v.swapaxes(1, 2), kv_len=kv_len)
    want = naive_attention(q, k, v, causal=False, kv_len=kv_len)
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_mla_attention_shapes_and_decode():
    import repro.configs as C
    from repro.models.attention import mla_attention, mla_decode, mla_specs
    from repro.models.base import init_params

    cfg = C.get("deepseek-v2-236b").smoke()
    params = init_params(mla_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    b, s = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, cfg.d_model),
                          jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    out = mla_attention(params, cfg, x, pos, impl="naive")
    assert out.shape == (b, s, cfg.d_model)

    # absorbed decode vs teacher-forced full attention on the last token
    ckv = jnp.zeros((b, s, cfg.kv_lora_rank), jnp.float32)
    krope = jnp.zeros((b, s, cfg.rope_head_dim), jnp.float32)
    outs = []
    for t in range(s):
        o, c_row, k_row = mla_decode(params, cfg, x[:, t:t + 1], ckv, krope,
                                     t)
        ckv, krope = ckv.at[:, t].set(c_row[:, 0]), krope.at[:, t].set(k_row[:, 0])
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(dec, out, atol=1e-3, rtol=1e-2)


def test_chunked_scan_reference_matches_naive():
    """The secondary scan-based reference (chunked_attention) stays honest
    against the naive oracle (it is kept as documentation of the non-VJP
    formulation)."""
    from repro.models.attention import chunked_attention

    b, s, h, kvh, d = 1, 96, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    for causal in (True, False):
        got = chunked_attention(q, k, v, causal=causal, q_chunk=32,
                                kv_chunk=24)
        want = naive_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


def test_yarn_frequencies_and_scale_match_published():
    """DeepSeek-V2's rope: 64 dims, base 1e4, YaRN factor 40 over an original
    4096 positions, beta_fast 32, beta_slow 1. Dimension i (of 32 pairs)
    turns 4096 * f_i / (2 pi) times over the original context; pairs that
    turn more than 32 times (i <= 10) keep f_i, those that turn less than
    once (i >= 23) take f_i / 40, and a linear ramp joins them."""
    import math

    import repro.configs as C
    from repro.models.attention import mla_softmax_scale
    from repro.models.layers import yarn_frequencies, yarn_mscale

    got = np.asarray(yarn_frequencies(64, 1e4, 40.0, 4096, 32.0, 1.0), np.float64)
    f = 1e4 ** (-np.arange(32) / 32)
    turns = 4096 * f / (2 * math.pi)
    assert turns[10] > 32 > turns[11] and turns[22] > 1 > turns[23]
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(got, f * (1 - ramp) + f / 40 * ramp, rtol=1e-6)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(1.2608, abs=1e-4)
    assert mla_softmax_scale(C.get("deepseek-v2-236b")) == pytest.approx(
        192 ** -0.5 * 1.2608 ** 2, rel=1e-4)


def test_mla_rope_rotates_interleaved_pairs():
    """The published model rotates rope dims (2i, 2i + 1) together and
    returns them de-interleaved: pair i lands at (i, i + r/2)."""
    import dataclasses

    import repro.configs as C
    from repro.models.attention import mla_rope
    from repro.models.layers import yarn_frequencies

    cfg = dataclasses.replace(C.get("deepseek-v2-236b"), yarn_factor=1.0)
    r, pos = cfg.rope_head_dim, 7
    f = np.asarray(yarn_frequencies(r, cfg.rope_theta, 1.0, 4096, 32.0, 1.0))
    for i in (0, 5, 31):
        x = jnp.zeros((1, 1, 1, r)).at[..., 2 * i].set(1.0).at[..., 2 * i + 1].set(2.0)
        out = np.asarray(mla_rope(cfg, x, jnp.array([[pos]]))[0, 0, 0])
        c, s = np.cos(pos * f[i]), np.sin(pos * f[i])
        want = np.zeros(r)
        want[i], want[i + r // 2] = c - 2 * s, 2 * c + s
        np.testing.assert_allclose(out, want, atol=1e-5)
