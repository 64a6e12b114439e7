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
