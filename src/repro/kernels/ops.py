"""jit'd dispatch wrappers for the Pallas kernels.

Kernels compile for the TPU by default. ``interpret=True`` runs the kernel
body through the Pallas interpreter instead, which is how the CPU tests
check them; the caller says which, so a run that lands on the wrong
backend fails instead of silently interpreting. The model layer calls
these via the ``pallas`` MSM policy.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_decode import flash_decode_pallas
from repro.kernels.fused_ffn import fused_ffn_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


@partial(jax.jit, static_argnames=("causal", "scale", "interpret"))
def flash_attention_op(q, k, v, *, causal: bool = True, scale=None,
                       interpret: bool = False):
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "interpret"))
def flash_decode_op(q, k, v, kv_len, *, scale=None, interpret: bool = False):
    return flash_decode_pallas(q, k, v, kv_len, scale=scale,
                               interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def fused_ffn_op(x, w_gate, w_up, w_down, *, interpret: bool = False):
    return fused_ffn_pallas(x, w_gate, w_up, w_down, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_op(x, dt, A, b_, c_, chunk: int = 128, *,
                interpret: bool = False):
    return ssd_scan_pallas(x, dt, A, b_, c_, chunk=chunk,
                           interpret=interpret)


def _tile(dim: int) -> int:
    """The largest multiple of 128 up to 1024 that divides ``dim``; the
    whole of ``dim`` where there is none."""
    return next((t for t in range(1024, 127, -128) if dim % t == 0), dim)


@partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul_op(lhs, rhs, group_sizes, *, interpret: bool = False):
    """lhs (m, k), rows sorted by group; rhs (groups, k, n); group_sizes
    (groups,) int32 -> (m, n) in lhs's dtype, accumulated in float32.

    The megablox grouped matmul: it visits no tile of an empty group, so an
    empty group's weights are never read, and rhs may be a whole stack of
    which a few groups have rows. Rows past the last group come back
    undefined. Rows are padded up to the row tile, 128: a decode step routes
    a few rows to each group, and a taller tile would only add MXU passes
    over the same weights."""
    (m, k), n, tm = lhs.shape, rhs.shape[2], 128
    out = gmm(jnp.pad(lhs, ((0, -m % tm), (0, 0))), rhs, group_sizes,
              preferred_element_type=lhs.dtype,
              tiling=(tm, _tile(k), _tile(n)), interpret=interpret)
    return out[:m]
