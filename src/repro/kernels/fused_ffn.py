"""Pallas TPU fused SwiGLU FFN: three GEMMs, zero HBM round-trips for the
hidden state.

Unfused, the (T x F) gate/up/hidden tensors cost 6*T*F bytes of HBM traffic
per layer; fused, HBM sees only x, the three weight tiles and y — the same
traffic the paper's L3 would have filtered (its Fig 4 'adjacent-kernel
reuse' band). Grid: (T blocks, F blocks), F innermost; the down-projection
partial products accumulate in an fp32 VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, y_ref, acc_scr, *,
                num_f: int):
    fi = pl.program_id(1)

    @pl.when(fi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Operands stay in their storage dtype (bf16 on the MXU's native path);
    # upcast copies of the weight tiles would not fit the scoped VMEM limit.
    x = x_ref[...]                               # (Bt, D)
    g = jax.lax.dot_general(x, wg_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    u = jax.lax.dot_general(x, wu_ref[...], (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    h = (g * jax.lax.logistic(g)) * u            # silu(g) * u, (Bt, Bf) f32
    wd = wd_ref[...]                             # (Bf, D)
    acc_scr[...] += jax.lax.dot_general(
        h.astype(wd.dtype), wd, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(fi == num_f - 1)
    def _finalize():
        y_ref[...] = acc_scr[...].astype(y_ref.dtype)


def fused_ffn_pallas(x, w_gate, w_up, w_down, *, block_t: int = 256,
                     block_f: int = 256, interpret: bool = False):
    """x: (T,D); w_gate/w_up: (D,F); w_down: (F,D) -> (T,D)."""
    t, d = x.shape
    f = w_gate.shape[1]
    block_t = min(block_t, t)
    block_f = min(block_f, f)
    assert t % block_t == 0 and f % block_f == 0
    grid = (t // block_t, f // block_f)
    kernel = functools.partial(_ffn_kernel, num_f=grid[1])
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, fi: (ti, 0)),
            pl.BlockSpec((d, block_f), lambda ti, fi: (0, fi)),
            pl.BlockSpec((d, block_f), lambda ti, fi: (0, fi)),
            pl.BlockSpec((block_f, d), lambda ti, fi: (fi, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda ti, fi: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        interpret=interpret,
    )(x, w_gate, w_up, w_down)
