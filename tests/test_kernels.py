"""Pallas kernel validation: shape/dtype sweeps against the jnp oracles,
interpret=True (the kernel body executes in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.fused_ffn import fused_ffn_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,s,h,kvh,d,causal,bq,bk", [
    (2, 256, 4, 2, 64, True, 128, 128),
    (1, 512, 8, 8, 64, True, 256, 128),
    (2, 256, 4, 1, 32, False, 128, 256),
    (1, 384, 4, 4, 128, True, 128, 128),
    (1, 256, 8, 2, 64, False, 64, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, s, h, kvh, d, causal, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, d), dtype)
    got = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_kv=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("b,h,kvh,d,s,kv_len,bk", [
    (2, 8, 2, 64, 1024, 700, 256),
    (1, 4, 4, 128, 512, 512, 128),
    (4, 16, 2, 64, 2048, 1, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_kernel(b, h, kvh, d, s, kv_len, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, d), dtype)
    got = flash_decode_pallas(q, k, v, kv_len, block_kv=bk, interpret=True)
    want = ref.flash_decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("t,d,f,bt,bf", [
    (256, 128, 512, 128, 256),
    (512, 256, 1024, 256, 512),
    (128, 64, 256, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ffn_kernel(t, d, f, bt, bf, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    x = (jax.random.normal(ks[0], (t, d), dtype) * 0.5).astype(dtype)
    wg = (jax.random.normal(ks[1], (d, f), dtype) * 0.05).astype(dtype)
    wu = (jax.random.normal(ks[2], (d, f), dtype) * 0.05).astype(dtype)
    wd = (jax.random.normal(ks[3], (f, d), dtype) * 0.05).astype(dtype)
    got = fused_ffn_pallas(x, wg, wu, wd, block_t=bt, block_f=bf,
                           interpret=True)
    want = ref.fused_ffn_ref(x, wg, wu, wd)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32),
                               atol=5 * TOL[dtype], rtol=5 * TOL[dtype])


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 256, 4, 32, 16, 64),
    (1, 128, 2, 64, 32, 32),
    (1, 512, 8, 16, 8, 128),
])
def test_ssd_scan_kernel(b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.3)
    b_ = jax.random.normal(ks[3], (b, s, n), jnp.float32) * 0.3
    c_ = jax.random.normal(ks[4], (b, s, n), jnp.float32) * 0.3
    got = ssd_scan_pallas(x, dt, A, b_, c_, chunk=chunk, interpret=True)
    want, _ = ref.ssd_chunk_ref(x, dt, A, b_, c_)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("op", ["flash_attention", "flash_decode",
                                "fused_ffn", "ssd_scan"])
def test_ops_dispatch_runs_interpreted_when_asked(op):
    """The jit'd dispatch wrappers compile for the TPU unless the caller
    passes interpret=True, as every CPU test does."""
    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    f32 = jnp.float32
    if op == "flash_attention":
        q, k, v = (jax.random.normal(ks[i], (1, 256, 4, 64), f32)
                   for i in range(3))
        got = ops.flash_attention_op(q, k, v, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
    elif op == "flash_decode":
        q = jax.random.normal(ks[0], (2, 4, 64), f32)
        k, v = (jax.random.normal(ks[i], (2, 512, 2, 64), f32)
                for i in (1, 2))
        got = ops.flash_decode_op(q, k, v, 300, interpret=True)
        want = ref.flash_decode_ref(q, k, v, 300)
    elif op == "fused_ffn":
        x = jax.random.normal(ks[0], (256, 128), f32) * 0.5
        wg, wu = (jax.random.normal(ks[i], (128, 512), f32) * 0.05
                  for i in (1, 2))
        wd = jax.random.normal(ks[3], (512, 128), f32) * 0.05
        got = ops.fused_ffn_op(x, wg, wu, wd, interpret=True)
        want = ref.fused_ffn_ref(x, wg, wu, wd)
    else:
        x = jax.random.normal(ks[0], (1, 128, 2, 32), f32) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 128, 2), f32))
        A = -jnp.exp(jax.random.normal(ks[2], (2,), f32) * 0.3)
        b_, c_ = (jax.random.normal(ks[i], (1, 128, 16), f32) * 0.3
                  for i in (3, 4))
        got = ops.ssd_scan_op(x, dt, A, b_, c_, chunk=64, interpret=True)
        want, _ = ref.ssd_chunk_ref(x, dt, A, b_, c_)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-3)


def test_ssd_jnp_chunked_matches_sequential():
    """The model-layer chunked SSD (lax.scan path used under pjit) agrees
    with the token-by-token recurrence for multiple chunk sizes."""
    from repro.models.ssm import ssd_chunked

    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    b, s, h, p, n = 2, 96, 4, 16, 8
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32) * 0.3)
    b_ = jax.random.normal(ks[3], (b, s, n), jnp.float32) * 0.3
    c_ = jax.random.normal(ks[4], (b, s, n), jnp.float32) * 0.3
    want, st_want = ref.ssd_chunk_ref(x, dt, A, b_, c_)
    for chunk in (16, 32, 96):
        got, st_got = ssd_chunked(x, dt, A, b_, c_, chunk=chunk)
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)
        np.testing.assert_allclose(st_got, st_want, atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("b,s,h,kvh,d,causal", [
    (1, 256, 4, 2, 32, True),
    (2, 128, 2, 2, 64, False),
])
def test_flash_attention_bwd_kernels(b, s, h, kvh, d, causal):
    """Pallas dq/dkv kernels vs autodiff of the naive oracle."""
    from repro.kernels.flash_attention_bwd import flash_attention_bwd_pallas
    from repro.models.attention import naive_attention

    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    dout = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

    # forward reference: out + lse
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d)
    sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (d ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask[None, None, None], sc, -1e30)
    lse = jax.nn.logsumexp(sc, axis=-1)            # (b,kvh,g,s)
    lse = lse.transpose(0, 3, 1, 2).reshape(b, s, h)
    out = naive_attention(q, k, v, causal=causal)

    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, dout, causal=causal, block_q=64, block_kv=64,
        interpret=True)

    def f(q, k, v):
        return (naive_attention(q, k, v, causal=causal) * dout).sum()

    dq_r, dk_r, dv_r = jax.grad(f, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(dq, dq_r, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(dk, dk_r, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(dv, dv_r, atol=2e-4, rtol=2e-4)
