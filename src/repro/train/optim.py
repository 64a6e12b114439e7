"""AdamW with dtype-configurable state — built from scratch (no optax).

Mixed-precision recipes (selected by the software-MSM policy):

* ``float32`` moments + fp32 master weights — the classic recipe
  (14 bytes/param with bf16 params).
* ``bfloat16`` moments (+ optional master) — the capacity-specialized recipe
  for >100B models on 16GB chips; uses stochastic rounding on the param
  update when no master is kept (6 bytes/param).

State tensors inherit the parameter logical axes, so FSDP shards optimizer
state exactly like weights.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    moment_dtype: str = "float32"      # float32 | bfloat16
    master_weights: bool = True
    stochastic_rounding: bool = False  # SR on bf16 param updates (no master)
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def lr_at(cfg: OptimConfig, step):
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * cos)


def init_state(params, cfg: OptimConfig):
    mdt = jnp.bfloat16 if cfg.moment_dtype == "bfloat16" else jnp.float32
    state = {
        "step": jnp.zeros((), jnp.int32),
        "mu": jax.tree.map(lambda p: jnp.zeros_like(p, dtype=mdt), params),
        "nu": jax.tree.map(lambda p: jnp.zeros_like(p, dtype=mdt), params),
    }
    if cfg.master_weights:
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32), params)
    return state


def global_norm(tree):
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def _stochastic_round_bf16(key, x32):
    """Unbiased fp32 -> bf16 rounding via uniform dither of the truncated bits."""
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    noise = jax.random.bits(key, bits.shape, jnp.uint32) & jnp.uint32(0xFFFF)
    return jax.lax.bitcast_convert_type(
        (bits + noise) & jnp.uint32(0xFFFF0000), jnp.float32).astype(jnp.bfloat16)


def apply_updates(params, grads, state, cfg: OptimConfig, rng=None):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    lr = lr_at(cfg, step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-9))

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    mdt = jnp.bfloat16 if cfg.moment_dtype == "bfloat16" else jnp.float32

    flat_params, treedef = jax.tree.flatten(params)
    flat_grads = jax.tree.leaves(grads)
    flat_mu = jax.tree.leaves(state["mu"])
    flat_nu = jax.tree.leaves(state["nu"])
    flat_master = (jax.tree.leaves(state["master"])
                   if cfg.master_weights else [None] * len(flat_params))
    use_sr = cfg.stochastic_rounding and not cfg.master_weights and rng is not None
    keys = (jax.random.split(rng, len(flat_params))
            if use_sr else [None] * len(flat_params))

    new_p, new_mu, new_nu, new_master = [], [], [], []
    for p, g, mu, nu, mw, k in zip(flat_params, flat_grads, flat_mu, flat_nu,
                                   flat_master, keys):
        g32 = g.astype(jnp.float32) * scale
        mu32 = mu.astype(jnp.float32) * b1 + g32 * (1 - b1)
        nu32 = nu.astype(jnp.float32) * b2 + jnp.square(g32) * (1 - b2)
        upd = (mu32 / bc1) / (jnp.sqrt(nu32 / bc2) + cfg.eps)
        base = mw if mw is not None else p.astype(jnp.float32)
        p32 = base - lr * (upd + cfg.weight_decay * base)
        if mw is not None:
            new_master.append(p32)
            new_p.append(p32.astype(p.dtype))
        elif k is not None and p.dtype == jnp.bfloat16:
            new_p.append(_stochastic_round_bf16(k, p32))
        else:
            new_p.append(p32.astype(p.dtype))
        new_mu.append(mu32.astype(mdt))
        new_nu.append(nu32.astype(mdt))

    new_state = {
        "step": step,
        "mu": jax.tree.unflatten(treedef, new_mu),
        "nu": jax.tree.unflatten(treedef, new_nu),
    }
    if cfg.master_weights:
        new_state["master"] = jax.tree.unflatten(treedef, new_master)
    metrics = {"lr": lr, "grad_norm": gnorm}
    return jax.tree.unflatten(treedef, new_p), new_state, metrics


def state_shardings(param_shardings_tree, cfg: OptimConfig, mesh,
                    grad_compression: str | None = None):
    """Optimizer state (and the int8_ef error-feedback buffers) shards
    exactly like its parameters."""
    from jax.sharding import NamedSharding, PartitionSpec

    scalar = NamedSharding(mesh, PartitionSpec())
    out = {
        "step": scalar,
        "mu": param_shardings_tree,
        "nu": param_shardings_tree,
    }
    if cfg.master_weights:
        out["master"] = param_shardings_tree
    if grad_compression == "int8_ef":
        out["ef"] = param_shardings_tree
    return out
