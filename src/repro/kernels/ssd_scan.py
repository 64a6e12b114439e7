"""Pallas TPU Mamba-2 SSD chunk-scan kernel.

One (batch, head) slice per grid row; the chunk axis is the innermost grid
dimension so the (P x N) SSM state lives in VMEM scratch across chunks —
the inter-chunk recurrence never touches HBM. Within a chunk, the quadratic
"dual form" (C B^T ⊙ decay) runs on (L x L) VMEM tiles.

HBM traffic: x, dt, the cumulative log-decay (as a column and a row), B,
C, y once each + nothing for the state — the paper's traffic-filtering
argument applied to the SSM working set.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, segc_ref, segr_ref, b_ref, c_ref, y_ref,
                st_scr, *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    x = x_ref[...].reshape(chunk, -1).astype(jnp.float32)      # (L, P)
    dt = dt_ref[...].reshape(chunk, 1).astype(jnp.float32)     # (L, 1)
    li = segc_ref[...].reshape(chunk, 1)                       # (L, 1)
    lj = segr_ref[...].reshape(1, chunk)                       # (1, L)
    b = b_ref[...].reshape(chunk, -1).astype(jnp.float32)      # (L, N)
    c = c_ref[...].reshape(chunk, -1).astype(jnp.float32)      # (L, N)
    total = li[chunk - 1:, :]                                  # (1, 1)

    # intra-chunk: (C B^T ⊙ decay ⊙ dt_j) X
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L,L)
    decay = jnp.exp(li - lj)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(iota_j <= iota_i, cb * decay, 0.0)
    xdt = x * dt                                                # (L,P)
    y = jax.lax.dot_general(att, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += (C exp(seg)) @ state_in ; state update
    st_in = st_scr[...]                                         # (N, P)
    c_decay = c * jnp.exp(li)                                   # (L,N)
    y += jax.lax.dot_general(c_decay, st_in, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    decay_out = jnp.exp(total - li)                             # (L,1)
    bwt = b * decay_out      # dt already folded into xdt       # (L,N)
    st_new = jax.lax.dot_general(bwt, xdt, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (N,P)
    # (1,1) -> (N,1) -> (N,P): Mosaic broadcasts one tiled dim at a time.
    carry = jnp.exp(jnp.broadcast_to(total, (st_in.shape[0], 1)))
    st_scr[...] = st_new + carry * st_in

    y_ref[...] = y.reshape(y_ref.shape).astype(y_ref.dtype)


def ssd_scan_pallas(x, dt, A, b_, c_, *, chunk: int = 128,
                    interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); A: (H,); b_/c_: (B,S,N) -> y (B,S,H,P).

    The state is carried in VMEM across the chunk grid dim; the final state
    is not returned (training path — decode keeps its own O(1) state)."""
    bsz, s, h, p = x.shape
    n = b_.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    xr = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    # dt and the within-chunk cumulative log-decay seg = cumsum(dt * A) enter
    # as (S, 1) columns, and seg also as a (1, S) row: the kernel needs both
    # orientations, and Mosaic has neither a cumsum nor a lane<->sublane
    # reshape. Each block spans the full unit dim, so every block is aligned.
    dtr = dt.transpose(0, 2, 1).reshape(bsz * h, s)
    da = dtr.astype(jnp.float32) * jnp.tile(A.astype(jnp.float32), bsz)[:, None]
    seg = jnp.cumsum(da.reshape(bsz * h, nc, chunk), axis=-1).reshape(bsz * h, s)
    br = jnp.repeat(b_[:, None], h, 1).reshape(bsz * h, s, n)
    cr = jnp.repeat(c_[:, None], h, 1).reshape(bsz * h, s, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    col = pl.BlockSpec((1, chunk, 1), lambda bh, _, ci: (bh, ci, 0))
    out = pl.pallas_call(
        kernel,
        grid=(bsz * h, 1, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, _, ci: (bh, ci, 0)),
            col,
            col,
            pl.BlockSpec((1, 1, chunk), lambda bh, _, ci: (bh, 0, ci)),
            pl.BlockSpec((1, chunk, n), lambda bh, _, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, _, ci: (bh, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda bh, _, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(xr, dtr[..., None], seg[..., None], seg[:, None, :], br, cr)
    return out.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
