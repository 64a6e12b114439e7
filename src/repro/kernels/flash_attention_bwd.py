"""Pallas TPU flash-attention backward kernels (dq; dk+dv).

Completes the kernel set: the training path on TPU runs fwd
(``flash_attention.py``) + these two kernels via a custom VJP, with the
same VMEM-tiling contract — score blocks are recomputed from (q, k, lse)
and never touch HBM (flash-attention-2, arXiv:2307.08691).

Grids mirror the jnp custom-VJP reference in ``models/attention.py``:
  dq:  (B*KVH, nq, nk)  — kv innermost, dq accumulates in VMEM scratch
  dkv: (B*KVH, nk, nq)  — q innermost, dk/dv accumulate in VMEM scratch
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _p_block(q, k, lse, qi, ki, scale, causal, block_q, block_kv, rows):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        iq = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_kv), 0) % block_q
        ik = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_kv), 1)
        s = jnp.where(ik <= iq, s, NEG_INF)
    return jnp.exp(s - lse)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, block_q, block_kv, num_kv, group):
    qi, ki = pl.program_id(1), pl.program_id(2)
    rows = group * block_q

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].reshape(rows, -1).astype(jnp.float32)
    k = k_ref[...].reshape(block_kv, -1).astype(jnp.float32)
    v = v_ref[...].reshape(block_kv, -1).astype(jnp.float32)
    do = do_ref[...].reshape(rows, -1).astype(jnp.float32)
    lse = lse_ref[...].reshape(rows, 1)
    delta = delta_ref[...].reshape(rows, 1)

    p = _p_block(q, k, lse, qi, ki, scale, causal, block_q, block_kv, rows)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    acc_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv - 1)
    def _fin():
        dq_ref[...] = acc_scr[...].reshape(dq_ref.shape).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, block_q,
                block_kv, num_q, group):
    ki, qi = pl.program_id(1), pl.program_id(2)
    rows = group * block_q

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[...].reshape(rows, -1).astype(jnp.float32)
    k = k_ref[...].reshape(block_kv, -1).astype(jnp.float32)
    v = v_ref[...].reshape(block_kv, -1).astype(jnp.float32)
    do = do_ref[...].reshape(rows, -1).astype(jnp.float32)
    lse = lse_ref[...].reshape(rows, 1)
    delta = delta_ref[...].reshape(rows, 1)

    p = _p_block(q, k, lse, qi, ki, scale, causal, block_q, block_kv, rows)
    dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _fin():
        dk_ref[...] = dk_scr[...].reshape(dk_ref.shape).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].reshape(dv_ref.shape).astype(dv_ref.dtype)


def _prep(q, k, v, out, lse, dout):
    b, sq, h, d = q.shape
    _, skv, kvh, dv = v.shape
    g = h // kvh
    qr = (q.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4)
          .reshape(b * kvh, g, sq, d))
    dor = (dout.reshape(b, sq, kvh, g, dv).transpose(0, 2, 3, 1, 4)
           .reshape(b * kvh, g, sq, dv))
    # Row statistics keep a trailing unit lane dim, so the kernel reads them
    # as (G*Bq, 1) columns by merging leading dims only: Mosaic cannot cast
    # a lane vector (G, Bq) into a column.
    lser = (lse.reshape(b, sq, kvh, g).transpose(0, 2, 3, 1)
            .reshape(b * kvh, g, sq, 1))
    delta = jnp.sum(out.astype(jnp.float32) * dout.astype(jnp.float32), -1)
    deltar = (delta.reshape(b, sq, kvh, g).transpose(0, 2, 3, 1)
              .reshape(b * kvh, g, sq, 1))
    kr = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, dv)
    return qr, kr, vr, dor, lser, deltar


def flash_attention_bwd_pallas(q, k, v, out, lse, dout, *, causal=True,
                               scale=None, block_q: int = 256,
                               block_kv: int = 256, interpret=False):
    """Returns (dq, dk, dv). lse: (B,Sq,H) from the forward kernel/ref."""
    b, sq, h, d = q.shape
    _, skv, kvh, dvd = v.shape
    g = h // kvh
    scale = scale if scale is not None else d ** -0.5
    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    assert sq % block_q == 0 and skv % block_kv == 0
    nq, nk = sq // block_q, skv // block_kv
    qr, kr, vr, dor, lser, deltar = _prep(q, k, v, out, lse, dout)

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_kv=nk, group=g)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * kvh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, g, block_q, d), lambda bh, qi, ki: (bh, 0, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, dvd), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, g, block_q, dvd), lambda bh, qi, ki: (bh, 0, qi, 0)),
            pl.BlockSpec((1, g, block_q, 1), lambda bh, qi, ki: (bh, 0, qi, 0)),
            pl.BlockSpec((1, g, block_q, 1), lambda bh, qi, ki: (bh, 0, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, block_q, d),
                               lambda bh, qi, ki: (bh, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g * block_q, d), jnp.float32)],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, num_q=nq, group=g)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b * kvh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, g, block_q, d), lambda bh, ki, qi: (bh, 0, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, dvd), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, g, block_q, dvd), lambda bh, ki, qi: (bh, 0, qi, 0)),
            pl.BlockSpec((1, g, block_q, 1), lambda bh, ki, qi: (bh, 0, qi, 0)),
            pl.BlockSpec((1, g, block_q, 1), lambda bh, ki, qi: (bh, 0, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_kv, dvd), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * kvh, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b * kvh, skv, dvd), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, dvd), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr, dor, lser, deltar)

    dq_out = (dq.reshape(b, kvh, g, sq, d).transpose(0, 3, 1, 2, 4)
              .reshape(b, sq, h, d))
    dk_out = dk.reshape(b, kvh, skv, d).transpose(0, 2, 1, 3)
    dv_out = dv.reshape(b, kvh, skv, dvd).transpose(0, 2, 1, 3)
    return dq_out, dk_out, dv_out
