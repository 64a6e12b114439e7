"""Compile the device path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax, and it compiles for a topology that
is only described. It refuses what interpret mode accepts: blocks that are
not tile-aligned, kernels over the scoped-VMEM limit, programs that do not
fit the chip's memory. Nothing runs, so these tests say nothing about
results or speed.

This is the only test file that loads the TPU library. The topology is
described inside a module-scoped fixture, never at import, so that every
test worker collects the same tests and only the one given this file loads
the library.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

HBM_LIMIT = 15.75 * 2 ** 30   # what the v5e compiler lets one program use


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one; keep these out of it.
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases():
    from repro.kernels import ops
    from repro.kernels.flash_attention_bwd import flash_attention_bwd_pallas

    # granite-3-2b: d_model 2048, 32 heads over 8 KV heads of 64, d_ff 8192.
    # mamba2-1.3b: 64 SSD heads of 64, state 128, chunk 256.
    q, kv = (1, 2048, 32, 64), (1, 2048, 8, 64)
    return {
        "flash_attention": (ops.flash_attention_op, [q, kv, kv]),
        "flash_attention_bwd": (
            functools.partial(flash_attention_bwd_pallas, causal=True),
            [q, kv, kv, q, ((1, 2048, 32), jnp.float32), q]),
        "flash_decode": (
            lambda q, k, v: ops.flash_decode_op(q, k, v, 4096),
            [(8, 32, 64), (8, 4096, 8, 64), (8, 4096, 8, 64)]),
        "fused_ffn": (ops.fused_ffn_op,
                      [(2048, 2048), (2048, 8192), (2048, 8192),
                       (8192, 2048)]),
        "ssd_scan": (functools.partial(ops.ssd_scan_op, chunk=256),
                     [(1, 2048, 64, 64), (1, 2048, 64),
                      ((64,), jnp.float32), (1, 2048, 128),
                      (1, 2048, 128)]),
    }


@pytest.mark.parametrize("kernel", ["flash_attention", "flash_attention_bwd",
                                    "flash_decode", "fused_ffn", "ssd_scan"])
def test_kernel_compiles_at_real_width(one_chip, kernel):
    fn, args = _kernel_cases()[kernel]
    avals = [_sds(one_chip, *a) if isinstance(a[0], tuple)
             else _sds(one_chip, a) for a in args]
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_decode_step(sharding, cfg, batch: int, max_len: int):
    """The serving decode step compiled as the engine compiles it (cache
    donated), from shapes alone; returns (compiled, cache shapes)."""
    from repro.models import LanguageModel
    from repro.models.base import abstract_params
    from repro.serve.step import make_decode_step

    model = LanguageModel(cfg)
    params = jax.tree.map(lambda a: _sds(sharding, a.shape, a.dtype),
                          abstract_params(model.specs()))
    cache = jax.tree.map(lambda a: _sds(sharding, a.shape, a.dtype),
                         jax.eval_shape(lambda: model.init_cache(batch, max_len)))
    compiled = jax.jit(make_decode_step(model), donate_argnums=(1,)).lower(
        params, cache, _sds(sharding, (batch, 1), jnp.int32),
        _sds(sharding, (), jnp.int32),
        _sds(sharding, (2,), jnp.uint32)).compile()
    return compiled, cache


def test_granite_decode_step_fits_one_chip(one_chip):
    """The whole serving decode step of granite-3-2b at its published
    widths, all 40 layers, batch 8 against a 4096-token cache."""
    import repro.configs as configs

    compiled, _ = _compile_decode_step(one_chip, configs.get("granite-3-2b"),
                                       8, 4096)
    mem = compiled.memory_analysis()
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < HBM_LIMIT, peak / 2 ** 30


def _materialized(hlo: str, shapes) -> list[str]:
    """Instructions outside fused computations whose result is an array of
    one of ``shapes``, leaving out parameters, tuple plumbing, bitcasts and
    dynamic-update-slices (written in place)."""
    dims = "|".join(",".join(map(str, s)) for s in shapes)
    result = re.compile(rf"^\s*(ROOT )?%\S+ = \(?\w+\[({dims})\]")
    keep = re.compile(r" (parameter|get-tuple-element|tuple|bitcast|"
                      r"dynamic-update-slice)\(")
    found, fused = [], False
    for line in hlo.splitlines():
        if not line.startswith(" "):
            fused = line.startswith("%fused")
        elif not fused and result.match(line) and not keep.search(line):
            found.append(line.strip()[:120])
    return found


# what one chip holds of a layer, where it holds a share
SHARE = {"deepseek-v2-236b": dict(experts_held=20)}   # 8-way expert parallel


@pytest.mark.parametrize("arch, n_layers, batch, max_len", [
    ("granite-3-2b", 40, 32, 1024),        # the chat cell's shapes
    ("mistral-nemo-12b", 10, 16, 4096),    # one pipeline stage, reasoning's
    ("deepseek-v2-236b", 6, 128, 4096),    # 1 dense + 5 MoE layers, long_decode's
])
def test_decode_step_writes_only_new_rows(one_chip, arch, n_layers, batch,
                                          max_len):
    """The layers read the stacked KV (or latent) cache where it lies and
    the step writes only the new token's rows into the donated cache: no
    temporary near the cache's size, and no op that copies a whole stacked
    cache, one layer group's or one layer's slice of it."""
    import repro.configs as configs

    cfg = dataclasses.replace(configs.get(arch), n_layers=n_layers,
                              **SHARE.get(arch, {}))
    compiled, cache = _compile_decode_step(one_chip, cfg, batch, max_len)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 8, (temp / 2 ** 30, cache_bytes / 2 ** 30)
    groups = {n_layers, 1, n_layers - cfg.first_k_dense}
    shapes = [(n, *a.shape[1:]) for a in cache.values() for n in groups]
    shapes += [a.shape[1:] for a in cache.values()]
    assert not _materialized(compiled.as_text(), shapes)
