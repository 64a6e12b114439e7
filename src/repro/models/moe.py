"""Mixture-of-Experts: routing over every expert, a dropless layer over the
experts this chip holds.

* Router (float32, as published): softmax scores over all ``n_experts``.
  With ``n_group`` > 1, group-limited greedy (DeepSeek-V2): a group's score
  is its best expert's, the ``topk_group`` best groups stay, and the top-k
  are taken among their experts. The top-k weights are renormalised to sum
  to 1 (``norm_topk_prob``), or else multiplied by
  ``routed_scaling_factor``.
* The layer holds experts ``[experts_first, experts_first + experts_held)``,
  weights ``(held, d, ff)`` (all of them by default). It computes, for
  every token routed to a held expert, that expert's contribution, and drops
  none: the (token, expert) pairs are sorted by expert and each held
  expert's rows go through one grouped matmul (``jax.lax.ragged_dot``), so
  the work scales with the routed pairs, not with experts x capacity. Pairs
  routed to experts held elsewhere sort last and are computed by the chips
  that hold them.
* The decode scans pass the stacked experts of all MoE layers and the
  layer's place in that stack instead (``layer``): the grouped matmul then
  runs over the whole stack, flattened to groups, with rows only in the
  layer's groups, and reads that layer's experts where they lie. A slice of
  the stack handed to the grouped-matmul kernel would be copied out first,
  every layer of every step. On the TPU that kernel is megablox's, which
  visits no empty group (``kernels/ops.py``); elsewhere ``ragged_dot`` over
  the same groups. Training keeps one layer's weights and ``ragged_dot``.
* Shared experts (DeepSeek-style) see every token, on every chip.

The Switch-style load-balance loss over all experts is returned for
training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.base import P, Specs
from repro.models.layers import ffn, ffn_specs


def moe_specs(cfg: ModelConfig) -> Specs:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    held = cfg.experts_held
    s: Specs = {
        "router": P((d, e), ("embed", "experts"), init="small"),
        "w_gate": P((held, d, f), ("experts", "embed", "ff")),
        "w_up": P((held, d, f), ("experts", "embed", "ff")),
        "w_down": P((held, f, d), ("experts", "ff", "embed")),
    }
    if cfg.n_shared_experts:
        s["shared"] = ffn_specs(d, cfg.moe_d_ff * cfg.n_shared_experts)
    return s


EXPERTS = ("w_gate", "w_up", "w_down")


def split_experts(layers):
    """Stacked layer params -> (the rest, the stacked experts): what a decode
    scan takes as its per-layer ``xs``, and what it closes over to pass to
    ``moe_ffn`` whole. No experts (a dense stack): (layers, {})."""
    if "moe" not in layers:
        return layers, {}
    moe = layers["moe"]
    return (dict(layers, moe={n: v for n, v in moe.items() if n not in EXPERTS}),
            {n: moe[n] for n in EXPERTS})


def held_in_stack(w, sizes, layer):
    """The stack ``w`` (L_moe, held, a, b) as L_moe * held groups (a bitcast),
    and group sizes that are ``sizes`` at layer ``layer``'s held experts and
    zero elsewhere."""
    n, held = w.shape[:2]
    groups = jax.lax.dynamic_update_slice(
        jnp.zeros((n * held,), jnp.int32), sizes, (layer * held,))
    return w.reshape(n * held, *w.shape[2:]), groups


def _grouped_matmul(x, w, sizes, layer):
    if layer is None:
        return jax.lax.ragged_dot(x, w, sizes)
    return jax.lax.platform_dependent(
        x, *held_in_stack(w, sizes, layer),
        tpu=ops.grouped_matmul_op, default=jax.lax.ragged_dot)


def route(params, cfg: ModelConfig, x2d):
    """x2d: (T, d) -> (weights (T,k) float32, experts (T,k), aux_loss)."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    scores = probs
    if cfg.n_group > 1:
        t = probs.shape[0]
        grouped = probs.reshape(t, cfg.n_group, -1)
        _, best = jax.lax.top_k(grouped.max(axis=-1), cfg.topk_group)
        keep = jnp.zeros((t, cfg.n_group), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        scores = jnp.where(keep[..., None], grouped, 0.0).reshape(t, -1)
    weights, experts = jax.lax.top_k(scores, cfg.top_k)
    if cfg.norm_topk_prob:
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-20)
    else:
        weights = weights * cfg.routed_scaling_factor
    # Switch-style load-balance aux loss.
    e = cfg.n_experts
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[experts.reshape(-1)].add(
        1.0 / (experts.size)
    )
    aux = e * jnp.sum(me * ce)
    return weights, experts, aux


def moe_ffn(params, cfg: ModelConfig, x, layer=None):
    """x: (B, S, d) -> (B, S, d), aux_loss: the held experts' part of the
    routed result, plus the shared experts. The experts in ``params`` are one
    layer's, (held, d, ff); with ``layer``, every MoE layer's,
    (L_moe, held, d, ff), of which layer ``layer``'s are used."""
    b, s, d = x.shape
    t, k, held = b * s, cfg.top_k, cfg.experts_held
    x2d = x.reshape(t, d)
    with jax.named_scope("moe_route"):
        weights, experts, aux = route(params, cfg, x2d)
    with jax.named_scope("moe_dispatch"):
        local = experts.reshape(-1) - cfg.experts_first          # (T*k,)
        mine = (local >= 0) & (local < held)
        local = jnp.where(mine, local, held)     # held elsewhere: sorts last
        order = jnp.argsort(local, stable=True)
        token = order // k
        xs = x2d[token]                                          # (T*k, d)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
    with jax.named_scope("moe_experts"):
        g = _grouped_matmul(xs, params["w_gate"], sizes, layer)
        u = _grouped_matmul(xs, params["w_up"], sizes, layer)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        ys = _grouped_matmul(h, params["w_down"], sizes, layer)  # (T*k, d)
    with jax.named_scope("moe_dispatch"):
        back = jnp.argsort(order)                # each pair's sorted row
        # Rows past the last group hold whatever the grouped matmul left
        # there: select zeros, so nothing of theirs reaches the sum.
        ys = jnp.where(mine[:, None], ys[back], 0).reshape(t, k, d)
        y2d = jnp.einsum("tkd,tk->td", ys, weights,
                         preferred_element_type=jnp.float32)
    if cfg.n_shared_experts:
        with jax.named_scope("shared_experts"):
            y2d = y2d + ffn(params["shared"], x2d).astype(jnp.float32)
    return y2d.astype(x.dtype).reshape(b, s, d), aux
