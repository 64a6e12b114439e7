"""The MoE layer: routing as DeepSeek-V2 publishes it, a dropless layer over
the experts held, and shares of the experts that add up to the whole."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models.base import init_params
from repro.models.layers import ffn
from repro.models.moe import moe_ffn, moe_specs, route

# 8 experts in 4 groups of 2, top 2 groups, top 3 experts
CFG = dataclasses.replace(C.get("deepseek-v2-236b").smoke(), d_model=8,
                          n_shared_experts=1, moe_d_ff=16)


def _router_case(cfg, logits):
    """Router params and inputs whose router logits are ``logits`` (T, E)."""
    t, e = logits.shape
    x = jnp.zeros((t, cfg.d_model), jnp.float32).at[:, :e].set(logits)
    w = jnp.zeros((cfg.d_model, e), jnp.float32).at[:e].set(jnp.eye(e))
    return {"router": w}, x


def test_group_limited_routing_by_hand():
    # groups (5, 1) (4.9, 0) (4.8, 4.7) (0, 0): the best two groups are 0 and
    # 1, so expert 1 (logit 1) is chosen before experts 4 and 5 of group 2
    logits = jnp.array([[5.0, 1.0, 4.9, 0.0, 4.8, 4.7, 0.0, 0.0]])
    params, x = _router_case(CFG, logits)
    weights, experts, _ = route(params, CFG, x)
    probs = jax.nn.softmax(logits[0])
    assert sorted(experts[0].tolist()) == [0, 1, 2]
    # not renormalised; multiplied by routed_scaling_factor (16)
    for w, e in zip(weights[0], experts[0]):
        np.testing.assert_allclose(w, 16.0 * probs[e], rtol=1e-6)
    # plain top-k would take expert 4 instead of 1
    plain = dataclasses.replace(CFG, n_group=1, topk_group=1)
    assert sorted(route(params, plain, x)[1][0].tolist()) == [0, 2, 4]


def test_renormalised_routing_by_hand():
    cfg = dataclasses.replace(CFG, norm_topk_prob=True)
    logits = jnp.array([[5.0, 1.0, 4.9, 0.0, 4.8, 4.7, 0.0, 0.0]])
    params, x = _router_case(cfg, logits)
    weights, experts, _ = route(params, cfg, x)
    p = jax.nn.softmax(logits[0])[jnp.array([0, 2, 1])]
    np.testing.assert_allclose(sorted(weights[0].tolist()),
                               sorted((p / p.sum()).tolist()), rtol=1e-6)


def _naive(params, cfg, x2d):
    """Each token's chosen experts held here, one at a time, plus the shared
    experts."""
    weights, experts, _ = route(params, cfg, x2d)
    out = []
    for t in range(x2d.shape[0]):
        y = ffn(params["shared"], x2d[t])
        for w, e in zip(weights[t], experts[t]):
            i = int(e) - cfg.experts_first
            if 0 <= i < cfg.experts_held:
                y = y + w * ffn({k: params[k][i] for k in ("w_gate", "w_up", "w_down")},
                                x2d[t])
        out.append(y)
    return jnp.stack(out)


def test_no_token_dropped_when_all_pick_one_expert():
    """Every token routes to expert 3 first: a capacity grid of 1.25 x the
    fair share would drop most of them; the dropless layer computes all."""
    cfg = dataclasses.replace(CFG, top_k=2)
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(0), jnp.float32)
    t = 64
    x = jax.random.normal(jax.random.PRNGKey(1), (1, t, cfg.d_model))
    x = x.at[..., 0].set(3.0 + jnp.abs(x[..., 0]))
    params["router"] = params["router"].at[0, 3].set(10.0)
    _, experts, _ = route(params, cfg, x[0])
    assert (experts == 3).any(axis=1).all()
    got, _ = moe_ffn(params, cfg, x)
    np.testing.assert_allclose(got[0], _naive(params, cfg, x[0]), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("first, held", [(0, 8), (2, 3), (6, 2)])
def test_held_share_matches_naive(first, held):
    cfg = dataclasses.replace(CFG, experts_first=first, experts_held=held)
    params = init_params(moe_specs(cfg), jax.random.PRNGKey(2), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    got, _ = moe_ffn(params, cfg, x)
    np.testing.assert_allclose(got.reshape(32, -1), _naive(params, cfg, x.reshape(32, -1)),
                               rtol=2e-5, atol=2e-5)


def test_shares_add_up_to_the_uncut_layer():
    """Four chips of two experts each: their outputs, with the shared experts
    (which every chip computes) counted once, are the uncut layer's."""
    full = init_params(moe_specs(CFG), jax.random.PRNGKey(4), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, CFG.d_model))
    want, _ = moe_ffn(full, CFG, x)
    shared = ffn(full["shared"], x)
    total = shared
    for first in range(0, 8, 2):
        cfg = dataclasses.replace(CFG, experts_first=first, experts_held=2)
        part = dict(full, **{k: full[k][first:first + 2]
                             for k in ("w_gate", "w_up", "w_down")})
        y, _ = moe_ffn(part, cfg, x)
        total = total + (y - shared)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(want - shared).max()) > 0.1
