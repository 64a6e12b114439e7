"""Abstract input construction for the dry-run: ShapeDtypeStructs with
shardings attached — weak-type-correct, shardable, zero allocation."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import repro.configs as configs
from repro.core import msm
from repro.models import LanguageModel
from repro.models.base import abstract_params
from repro.sharding.partition import (batch_spec, cache_shardings,
                                      param_shard_count, param_shardings)
from repro.train import OptimConfig, init_opt_state

VLM_PATCHES = 256
WHISPER_ENC_LEN = 1500


def sds(shape, dtype, mesh=None, spec=None):
    sharding = NamedSharding(mesh, spec) if mesh is not None else None
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def abstract_model_params(model: LanguageModel, mesh: Mesh, fsdp: bool = True):
    specs = model.specs()
    aparams = abstract_params(specs)
    shardings = param_shardings(model.axes(), aparams, mesh, fsdp=fsdp)

    def attach(a, s):
        if isinstance(a, dict):
            return {k: attach(a[k], s[k]) for k in a}
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)

    return attach(aparams, shardings), shardings


def optim_config_for(policy, **schedule) -> OptimConfig:
    """The policy's optimizer recipe; ``schedule`` sets the OptimConfig
    learning-rate fields (lr, warmup_steps, total_steps)."""
    return OptimConfig(
        moment_dtype="bfloat16" if policy.optimizer_dtype == "bfloat16" else "float32",
        master_weights=policy.master_weights,
        # RTN updates in the capacity-specialized recipe: the SR path costs a
        # params-sized u32/u64 RNG temp per step (~7 GiB/device at 236B).
        stochastic_rounding=False,
        **schedule,
    )


def abstract_opt_state(model, aparams, opt_cfg: OptimConfig, mesh,
                       grad_compression=None):
    """eval_shape through the real initializer, then attach shardings that
    mirror the parameter shardings."""
    astate = jax.eval_shape(
        lambda p: init_opt_state(p, opt_cfg, grad_compression), aparams)

    def mirror(a, template):
        if isinstance(a, dict):
            return {k: mirror(a[k], template) for k in a}
        # scalars replicate; tensors inherit the matching param sharding by path
        return a

    # attach: walk astate alongside a params-shaped template where possible
    def attach(node, params_node):
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if k in ("mu", "nu", "master", "ef"):
                    out[k] = attach_tree_like_params(v, params_node)
                elif k == "step":
                    out[k] = jax.ShapeDtypeStruct(
                        v.shape, v.dtype,
                        sharding=NamedSharding(mesh, PartitionSpec()))
                else:
                    out[k] = attach(v, params_node)
            return out
        return node

    def attach_tree_like_params(node, params_node):
        if isinstance(node, dict):
            return {k: attach_tree_like_params(node[k], params_node[k])
                    for k in node}
        return jax.ShapeDtypeStruct(node.shape, node.dtype,
                                    sharding=params_node.sharding)

    return attach(astate, aparams)


def _sharding_of(tree):
    return jax.tree.map(lambda a: a.sharding, tree)


def input_specs(arch: str, shape_name: str, mesh: Mesh, policy=None):
    """Returns (step_kind, model, abstract_args, out_shardings) for the cell.

    out_shardings pin the step outputs (new params / opt state / cache) to
    the input shardings — without this XLA is free to materialize the
    optimizer math unsharded (observed: 26 GiB/device of fp32 temporaries on
    a 1.1B model) and donation cannot alias."""
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    policy = policy or msm.recommend(shape.name, cfg.n_params(),
                                     chips=param_shard_count(mesh))
    model = LanguageModel(cfg, impl=policy.attention_impl, remat=policy.remat)
    gb, seq = shape.global_batch, shape.seq_len
    bspec = batch_spec(mesh)
    tok_dtype = jnp.int32
    repl = NamedSharding(mesh, PartitionSpec())

    fsdp = policy.serve_fsdp if shape.step != "train" else True
    aparams, _ = abstract_model_params(model, mesh, fsdp=fsdp)

    if shape.step == "train":
        batch = {
            "tokens": sds((gb, seq), tok_dtype, mesh, bspec),
            "labels": sds((gb, seq), tok_dtype, mesh, bspec),
            # runtime positions: sequence packing support + keeps causal
            # masks from being constant-folded at score shape
            "positions": sds((gb, seq), tok_dtype, mesh, bspec),
        }
        if cfg.frontend == "vision":
            batch["patch_embeds"] = sds((gb, VLM_PATCHES, cfg.d_model),
                                        jnp.bfloat16, mesh, bspec)
        if cfg.family == "audio":
            batch["frames"] = sds((gb, seq, cfg.d_model), jnp.bfloat16, mesh,
                                  bspec)
            batch["tokens"] = sds((gb, seq // 4), tok_dtype, mesh, bspec)
            batch["labels"] = sds((gb, seq // 4), tok_dtype, mesh, bspec)
        opt_cfg = optim_config_for(policy)
        aopt = abstract_opt_state(model, aparams, opt_cfg, mesh,
                                  policy.grad_compression)
        rng = sds((2,), jnp.uint32, mesh, PartitionSpec())
        metrics_sh = {"lr": repl, "grad_norm": repl, "loss": repl}
        out_sh = (_sharding_of(aparams), _sharding_of(aopt), metrics_sh)
        return "train", model, (aparams, aopt, batch, rng), out_sh

    if shape.step == "prefill":
        batch = {"tokens": sds((gb, seq), tok_dtype, mesh, bspec),
                 "positions": sds((gb, seq), tok_dtype, mesh, bspec)}
        if cfg.frontend == "vision":
            batch["patch_embeds"] = sds((gb, VLM_PATCHES, cfg.d_model),
                                        jnp.bfloat16, mesh, bspec)
        if cfg.family == "audio":
            batch["frames"] = sds((gb, seq, cfg.d_model), jnp.bfloat16, mesh,
                                  bspec)
            batch["tokens"] = sds((gb, seq // 4), tok_dtype, mesh, bspec)
        out_sh = NamedSharding(mesh, bspec)
        return "prefill", model, (aparams, batch), out_sh

    # decode: one new token against a seq_len cache
    shard_seq = policy.kv_shard_axis == "data" or gb == 1
    kv_dtype = jnp.int8 if policy.kv_cache_dtype == "int8" else jnp.bfloat16
    acache = jax.eval_shape(
        lambda: model.init_cache(gb, seq, dtype=kv_dtype,
                                 enc_len=WHISPER_ENC_LEN))
    cshard = cache_shardings(acache, mesh, shard_seq=shard_seq)
    acache = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=cshard[k])
              for k, v in acache.items()}
    tokens = sds((gb, 1), tok_dtype, mesh,
                 bspec if gb > 1 else PartitionSpec())
    pos = sds((), jnp.int32, mesh, PartitionSpec())
    rng = sds((2,), jnp.uint32, mesh, PartitionSpec())
    out_sh = (tokens.sharding, _sharding_of(acache))
    return "decode", model, (aparams, acache, tokens, pos, rng), out_sh
