"""The MoE layer: routing as DeepSeek-V2 publishes it, a dropless layer over
the experts held, and shares of the experts that add up to the whole."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.kernels import ops
from repro.models.base import init_params
from repro.models.layers import ffn
from repro.models.moe import EXPERTS, held_in_stack, moe_ffn, moe_specs, route

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


# --- the decode scans' path: one layer's experts read from the whole stack ---

N_STACK = 3


def _stack_case(cfg, bias=None):
    """Three layers' params (each with its own router) and their experts
    stacked; ``bias`` (expert -> logit) steers every token's routes."""
    layers = [init_params(moe_specs(cfg), jax.random.PRNGKey(10 + i), jnp.float32)
              for i in range(N_STACK)]
    x = jax.random.normal(jax.random.PRNGKey(20), (2, 16, cfg.d_model))
    if bias is not None:
        x = x.at[..., 0].set(3.0 + jnp.abs(x[..., 0]))
        for p in layers:
            for e, v in bias.items():
                p["router"] = p["router"].at[0, e].set(v)
    stack = {n: jnp.stack([p[n] for p in layers]) for n in EXPERTS}
    return layers, stack, x


# (experts_first, experts_held, bias): tokens routed to experts held
# elsewhere; every token routed to one expert; no row for the held experts
# (every token's three experts lie in groups 0 and 1, experts 0-3).
STACK_CASES = {
    "routed_elsewhere": (2, 3, None),
    "all_to_one": (0, 8, {3: 10.0}),
    "held_get_no_rows": (6, 2, {0: 10.0, 1: 9.0, 2: 10.0, 3: 9.0}),
}


@pytest.mark.parametrize("case", sorted(STACK_CASES))
def test_stacked_experts_match_per_layer(case):
    """``moe_ffn`` given the stack and a layer's place in it equals
    ``moe_ffn`` given that layer's experts, at every place. Off the TPU both
    run ``ragged_dot``, over the same rows in the same groups (the stack's
    other groups are empty), so they agree to float32 rounding."""
    first, held, bias = STACK_CASES[case]
    cfg = dataclasses.replace(CFG, experts_first=first, experts_held=held)
    layers, stack, x = _stack_case(cfg, bias)
    for i, p in enumerate(layers):
        want, _ = moe_ffn(p, cfg, x)
        got, _ = moe_ffn(dict(p, **stack), cfg, x, layer=i)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        if case == "held_get_no_rows":   # the shared experts alone
            np.testing.assert_allclose(got, ffn(p["shared"], x), rtol=1e-6,
                                       atol=1e-6)


@pytest.mark.parametrize("case", sorted(STACK_CASES))
def test_grouped_matmul_kernel_reads_one_layer_of_the_stack(case):
    """The TPU kernel (interpreted), given the flattened stack and the group
    sizes ``moe_ffn`` builds for layer i, equals ``ragged_dot`` on layer i's
    experts in every row routed to them (the rows past the last group are
    undefined, and ``moe_ffn`` selects zeros there). Float32 operands: the
    two may sum each row's products in a different order (the kernel into
    a float32 accumulator, per tile of k), so they agree to float32
    rounding of the sum."""
    first, held, bias = STACK_CASES[case]
    cfg = dataclasses.replace(CFG, experts_first=first, experts_held=held)
    layers, stack, x = _stack_case(cfg, bias)
    for i, p in enumerate(layers):
        _, experts, _ = route(p, cfg, x.reshape(-1, cfg.d_model))
        local = experts.reshape(-1) - first
        local = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(local, stable=True)
        rows = x.reshape(-1, cfg.d_model)[order // cfg.top_k]
        sizes = jnp.zeros((held + 1,), jnp.int32).at[local].add(1)[:held]
        mine = int(sizes.sum())
        if case == "held_get_no_rows":
            assert mine == 0
        elif case == "all_to_one":
            assert int(sizes[3]) == x.shape[0] * x.shape[1]
        hidden = jax.random.normal(jax.random.PRNGKey(30 + i),
                                   (rows.shape[0], cfg.moe_d_ff))
        for n in EXPERTS:
            w = stack[n]
            lhs = hidden if n == "w_down" else rows
            got = ops.grouped_matmul_op(lhs, *held_in_stack(w, sizes, i),
                                        interpret=True)
            want = jax.lax.ragged_dot(lhs, w[i], sizes)
            scale = float(jnp.abs(want[:mine]).max()) if mine else 1.0
            np.testing.assert_allclose(got[:mine], want[:mine], rtol=0,
                                       atol=1e-5 * scale)


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "qwen3-moe-235b-a22b"])
def test_decode_step_matches_per_layer_experts(arch, monkeypatch):
    """A decode step whose MoE layers read their experts from the stack gives
    the logits of the formulation it replaced, in which each layer's experts
    are its own slice of the stack (a per-layer ``moe_ffn``), over steps
    that feed back the chosen tokens. Both run ``ragged_dot`` on the CPU over
    the same groups; the tolerance is bfloat16's resolution, 2**-7 of the
    logits' scale, for the parameters and activations are bfloat16."""
    from repro.models import LanguageModel, moe as moe_mod

    cfg = C.get(arch).smoke()
    model = LanguageModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 1), 0, cfg.vocab_size)

    def steps():
        step = jax.jit(lambda *a: model.decode_step(*a))
        cache, tok, out = model.init_cache(4, 8), tokens, []
        for pos in range(4):
            logits, cache = step(params, cache, tok, jnp.int32(pos))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(np.asarray(logits, np.float32))
        return np.stack(out)

    got = steps()
    per_layer = moe_mod.moe_ffn

    def sliced(p, cfg_, x, layer=None):
        return per_layer(dict(p, **{n: p[n][layer] for n in EXPERTS}), cfg_, x)

    monkeypatch.setattr(moe_mod, "moe_ffn", sliced)
    want = steps()
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2 ** -7 * np.abs(want).max())
