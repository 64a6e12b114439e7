"""The kernel x shape matrix the analyzer runs over.

Import-light on purpose: jax and the kernel modules load lazily inside the
builders, so ``repro.workloads.registry`` can enumerate ``kernel.*``
scenario names without paying the jax import.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Callable


@dataclass(frozen=True)
class KernelCase:
    """One (kernel, shape) point: ``build()`` abstract-traces it."""

    kernel: str
    case: str
    build: Callable[[], list]        # -> list[KernelFacts]

    @property
    def name(self) -> str:
        return f"{self.kernel}.{self.case}"


def _sds(shape, dt: str):
    import jax
    import jax.numpy as jnp
    return jax.ShapeDtypeStruct(shape, getattr(jnp, dt))


def _flash_attention(case, b, s, h, kvh, d, dt, causal, block):
    def build():
        from repro.check.facts import trace_kernel
        from repro.kernels.flash_attention import flash_attention_pallas
        q = _sds((b, s, h, d), dt)
        k = _sds((b, s, kvh, d), dt)
        v = _sds((b, s, kvh, d), dt)
        return trace_kernel(flash_attention_pallas, q, k, v, case=case,
                            causal=causal, block_q=block, block_kv=block)
    return KernelCase("flash_attention", case, build)


def _flash_attention_bwd(case, b, s, h, kvh, d, dt, causal, block):
    def build():
        from repro.check.facts import trace_kernel
        from repro.kernels.flash_attention_bwd import (
            flash_attention_bwd_pallas)
        q = _sds((b, s, h, d), dt)
        k = _sds((b, s, kvh, d), dt)
        v = _sds((b, s, kvh, d), dt)
        out = _sds((b, s, h, d), dt)
        lse = _sds((b, s, h), "float32")
        dout = _sds((b, s, h, d), dt)
        return trace_kernel(flash_attention_bwd_pallas, q, k, v, out, lse,
                            dout, case=case, causal=causal, block_q=block,
                            block_kv=block)
    return KernelCase("flash_attention_bwd", case, build)


def _flash_decode(case, b, s, h, kvh, d, dt, block_kv):
    def build():
        from repro.check.facts import trace_kernel
        from repro.kernels.flash_decode import flash_decode_pallas
        q = _sds((b, h, d), dt)
        k = _sds((b, s, kvh, d), dt)
        v = _sds((b, s, kvh, d), dt)
        return trace_kernel(flash_decode_pallas, q, k, v, s, case=case,
                            block_kv=block_kv)
    return KernelCase("flash_decode", case, build)


def _fused_ffn(case, t, d, f, dt, block_t, block_f):
    def build():
        from repro.check.facts import trace_kernel
        from repro.kernels.fused_ffn import fused_ffn_pallas
        x = _sds((t, d), dt)
        wg = _sds((d, f), dt)
        wu = _sds((d, f), dt)
        wd = _sds((f, d), dt)
        return trace_kernel(fused_ffn_pallas, x, wg, wu, wd, case=case,
                            block_t=block_t, block_f=block_f)
    return KernelCase("fused_ffn", case, build)


def _ssd_scan(case, b, s, h, p, n, dt, chunk):
    def build():
        from repro.check.facts import trace_kernel
        from repro.kernels.ssd_scan import ssd_scan_pallas
        x = _sds((b, s, h, p), dt)
        dtt = _sds((b, s, h), dt)
        a = _sds((h,), "float32")
        b_ = _sds((b, s, n), dt)
        c_ = _sds((b, s, n), dt)
        return trace_kernel(ssd_scan_pallas, x, dtt, a, b_, c_, case=case,
                            chunk=chunk)
    return KernelCase("ssd_scan", case, build)


CASES: tuple[KernelCase, ...] = (
    # GQA training-shape forward, bf16 + a single-head fp32 point.
    _flash_attention("b2s512", b=2, s=512, h=8, kvh=4, d=128, dt="bfloat16",
                     causal=True, block=256),
    _flash_attention("b1s1024f32", b=1, s=1024, h=4, kvh=4, d=128,
                     dt="float32", causal=False, block=256),
    _flash_attention_bwd("b2s512", b=2, s=512, h=8, kvh=4, d=128,
                         dt="bfloat16", causal=True, block=256),
    # Decode: long-KV bandwidth-bound cells (the serve pricing shape).
    _flash_decode("b2s2048", b=2, s=2048, h=8, kvh=4, d=128, dt="bfloat16",
                  block_kv=512),
    _flash_decode("b1s4096", b=1, s=4096, h=8, kvh=8, d=128, dt="bfloat16",
                  block_kv=512),
    _fused_ffn("t512d1024", t=512, d=1024, f=2048, dt="bfloat16",
               block_t=256, block_f=512),
    _fused_ffn("t256d512f32", t=256, d=512, f=1024, dt="float32",
               block_t=256, block_f=512),
    _ssd_scan("b2s1024", b=2, s=1024, h=4, p=64, n=128, dt="bfloat16",
              chunk=128),
    # Published widths (granite-3-2b; mamba2-1.3b for the SSD scan), with
    # the blocks the kernels use by default: the same points that
    # tests/test_chip_compile.py compiles for a v5e, so R1/R5 judge what
    # the compiler judges.
    _flash_attention("granite2b_s2048", b=1, s=2048, h=32, kvh=8, d=64,
                     dt="bfloat16", causal=True, block=256),
    _flash_attention_bwd("granite2b_s2048", b=1, s=2048, h=32, kvh=8, d=64,
                         dt="bfloat16", causal=True, block=256),
    _flash_decode("granite2b_b8s4096", b=8, s=4096, h=32, kvh=8, d=64,
                  dt="bfloat16", block_kv=512),
    _fused_ffn("granite2b_t2048", t=2048, d=2048, f=8192, dt="bfloat16",
               block_t=256, block_f=256),
    _ssd_scan("mamba2_s2048", b=1, s=2048, h=64, p=64, n=128, dt="bfloat16",
              chunk=256),
)

_BY_NAME = {c.name: c for c in CASES}


def case_names() -> list[str]:
    return [c.name for c in CASES]


def get(name: str) -> KernelCase:
    try:
        return _BY_NAME[name]
    except KeyError:
        raise KeyError(f"unknown kernel case {name!r}; "
                       f"known: {case_names()}") from None


@lru_cache(maxsize=None)
def trace_case(name: str) -> tuple:
    """Build (and memoize) the KernelFacts for one catalog case."""
    return tuple(get(name).build())


def trace_all() -> list:
    """KernelFacts for every case in the matrix, in catalog order."""
    out = []
    for case in CASES:
        out.extend(trace_case(case.name))
    return out
