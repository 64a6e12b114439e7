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
        o, ckv, krope = mla_decode(params, cfg, x[:, t:t + 1], ckv, krope,
                                   t)
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
