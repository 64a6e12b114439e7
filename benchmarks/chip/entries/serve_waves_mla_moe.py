"""Adapter: closed-loop waves through ``ServingEngine.generate``, for a
DeepSeek-V2-style model (latent attention, routed and shared experts) on
one chip's share of its experts.

The waves, clocks, window, trace and sampling are ``serve_waves.py``'s,
run from a private instance of that module in which two functions are this
file's: ``build`` (the seeded weights of ``weights_mla_moe.py``, renamed
into the program's tree) and ``compare`` (the served tokens against
``reference/mla_moe.py``). ``compare`` reads the share of the served
tokens' logit gaps over a limit, the widest, and the 90th percentile, and
logs how many compared positions have a layer whose router margin (last
expert chosen against the next, last group kept against the next, in
router logits) lies under each of ``MARGINS``: there a bfloat16 program
can route otherwise than the float32 reference.
"""
from __future__ import annotations

import importlib.util
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import weights_mla_moe as W  # noqa: E402
from reference import mla_moe  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "serve_waves_for_mla_moe", os.path.join(HERE, "serve_waves.py"))
waves = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(waves)

MARGINS = (0.001, 0.003, 0.01, 0.03, 0.1)


def _attn(layers: dict) -> dict:
    names = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo")
    return {**{k: layers[k] for k in names},
            "wq_a_norm": {"scale": layers["wq_a_norm"]},
            "wkv_a_norm": {"scale": layers["wkv_a_norm"]}}


def program_params(cfg: dict, top: dict, dense: dict, moe: dict) -> dict:
    """The benchmark's weights, renamed into the program's parameter tree
    (no copy)."""
    def block(layers, ffn):
        return {"ln1": {"scale": layers["attn_norm"]}, "attn": _attn(layers),
                "ln2": {"scale": layers["ffn_norm"]}, **ffn}

    expert = ("w_gate", "w_up", "w_down")
    return {
        "emb": {"embedding": top["embedding"], "lm_head": top["lm_head"]},
        "ln_f": {"scale": top["final_norm"]},
        "dense_layers": block(dense, {"ffn": {k: dense[k] for k in expert}}),
        "layers": block(moe, {"moe": {
            "router": moe["router"], **{k: moe[k] for k in expert},
            "shared": {k: moe["shared_" + k[2:]] for k in expert}}}),
    }


def build(cell, seed: int):
    """The engine, its decode step compiled, holding the seed's weights."""
    from repro.configs.base import ModelConfig
    from repro.launch.serve import ServingEngine
    from repro.models import LanguageModel

    mc, traffic = cell.config["model_config"], cell.traffic
    model = LanguageModel(ModelConfig(**mc))
    params = program_params(mc, *W.make(mc, seed))
    waves._same_layout(params, model)
    engine = ServingEngine(model, params, traffic["batch"], traffic["max_len"])
    engine.compile()
    return engine


def compare(cell, records, prompts, outputs, sample, control) -> dict:
    """The logit gaps, under the float32 reference, of the served tokens of
    the sampled requests (with ``control``, also the fp8 control's): the
    share of them over the limits file's ``gap``, the widest, and the 90th
    percentile; the router margins are logged.

    ``correct`` compares the share. Routing is discrete: where the last
    expert chosen and the next (or the last group kept and the next) lie
    within a bfloat16 program's error of each other, the program can take
    the other route, and with the routed experts' weights scaled by 16 that
    moves the position's logits by up to a few logits. The widest gap then
    reads such a route and not a fault. Few positions' gaps leave rounding
    size that way, while a fault at one position in twenty, or a lower
    precision, puts many more over ``gap``."""
    mc, max_len = cell.config["model_config"], cell.traffic["max_len"]
    n_served = max(records[i]["G"] for i, _ in sample)
    tokens = np.zeros((len(sample), max_len), np.int32)
    rows = np.zeros((len(sample), n_served), np.int32)
    served = np.zeros((len(sample), n_served), np.int32)
    live = np.zeros((len(sample), n_served), bool)
    for s, (i, j) in enumerate(sample):
        p, g = records[i]["P"], records[i]["G"]
        tokens[s, :p + g] = np.concatenate([prompts[i][j], outputs[i][j]])
        # output token t was chosen by the logits at position p - 1 + t;
        # short rows repeat their last pair, which changes no maximum
        r = np.minimum(np.arange(n_served), g - 1)
        rows[s], served[s], live[s] = p - 1 + r, outputs[i][j][r], np.arange(n_served) < g
    t0 = time.perf_counter()
    gaps = mla_moe.served_gaps(mc, cell.seed, tokens, rows, served, control=control)
    gap, margin = gaps["gap"][live], gaps["margin"][live]
    cell.log(f"reference over {len(sample)} requests, {gap.size} served tokens: "
             f"{time.perf_counter() - t0:.1f}s")
    for m in MARGINS:
        near = margin < m
        cell.log(f"router margin < {m}: {int(near.sum())} of {gap.size} compared "
                 f"positions; widest gap there {gap[near].max(initial=0.0):.4f}, "
                 f"elsewhere {gap[~near].max(initial=0.0):.4f}")
    over = cell.limits["logit_gap_over_share"]["gap"]
    numbers = {"logit_gap_over_share": float(np.mean(gap > over)),
               "max_logit_gap": float(gap.max()),
               "logit_gap_p90": float(np.quantile(gap, 0.9))}
    cell.log(f"gaps over {over}: {int((gap > over).sum())} of {gap.size}")
    if control:
        ctl = gaps["control_gap"][live]
        numbers.update(control_logit_gap_over_share=float(np.mean(ctl > over)),
                       control_max_logit_gap=float(ctl.max()),
                       control_logit_gap_p90=float(np.quantile(ctl, 0.9)))
    return numbers


waves.build, waves.compare = build, compare
run = waves.run
