"""Operations and bytes that a DeepSeek-V2-style decoder requires, on one
chip's share of its experts, from configuration shapes.

As in ``counts.py``, these count the work of the model, never what an
implementation happens to do: latent attention in the absorbed form (the
token's query meets the cached latents; no per-head keys or values are
rebuilt), no reads of cache positions that hold nothing yet, no padding.
``cfg`` is a configuration file's ``model_config``.

Which held experts a step touches depends on its tokens' routes. The counts
take the expectation under routing that is uniform over the experts (which
the group-limited router keeps, by symmetry, at random weights): a token
routes each of its ``top_k`` picks to a held expert with chance held / E,
and a held expert is touched by a step of ``batch`` tokens with chance
1 - (1 - top_k / E) ** batch.
"""
from __future__ import annotations

BF16 = 2


def _held(cfg: dict) -> int:
    return cfg.get("experts_held") or cfg["n_experts"]


def attn_params(cfg: dict) -> int:
    """Matrix weights of one latent-attention layer: query down and up,
    KV latent and rope key, the two latent-to-head maps (absorbed into the
    query and the output), output."""
    d, h, hd, vd = cfg["d_model"], cfg["n_heads"], cfg["head_dim"], cfg["v_head_dim"]
    r, ql, kvl = cfg["rope_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * ql + ql * h * (hd + r) + d * (kvl + r) + kvl * h * hd
            + kvl * h * vd + h * vd * d)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def moe_layers(cfg: dict) -> int:
    return cfg["n_layers"] - cfg["first_k_dense"]


def experts_touched(cfg: dict, batch: int) -> float:
    """Expected held experts of one MoE layer that a step's tokens reach."""
    return _held(cfg) * (1.0 - (1.0 - cfg["top_k"] / cfg["n_experts"]) ** batch)


def routed_pairs(cfg: dict, batch: int) -> float:
    """Expected (token, held expert) pairs of one MoE layer in a step."""
    return batch * cfg["top_k"] * _held(cfg) / cfg["n_experts"]


def token_params(cfg: dict) -> int:
    """Weights that multiply every token: attention, the dense layers' FFN,
    the router and shared experts, and the head."""
    d = cfg["d_model"]
    shared = 3 * d * cfg["moe_d_ff"] * cfg["n_shared_experts"]
    return (cfg["n_layers"] * attn_params(cfg)
            + cfg["first_k_dense"] * 3 * d * cfg["dense_d_ff"]
            + moe_layers(cfg) * (d * cfg["n_experts"] + shared)
            + d * cfg["vocab_size"])


def latent_bytes_per_position(cfg: dict) -> int:
    """Latent and rope-key bytes of one position of one request, all layers."""
    return cfg["n_layers"] * (cfg["kv_lora_rank"] + cfg["rope_head_dim"]) * BF16


def expert_flops(cfg: dict, batch: int) -> float:
    """Operations of the held experts in one step, all MoE layers."""
    return 2 * routed_pairs(cfg, batch) * expert_params(cfg) * moe_layers(cfg)


def expert_bytes(cfg: dict, batch: int) -> float:
    """Bytes the held experts must move in one step, all MoE layers: each
    touched expert's weights once, and each routed pair's input and output
    row."""
    per_layer = (experts_touched(cfg, batch) * expert_params(cfg)
                 + routed_pairs(cfg, batch) * 2 * cfg["d_model"]) * BF16
    return per_layer * moe_layers(cfg)


def decode_flops(cfg: dict, batch: int, pos: int) -> float:
    """Operations of one decode step: two per multiply-add of the weights
    each token meets, the held experts' routed pairs, and the absorbed
    attention over the pos + 1 attended positions in every layer (scores
    against latent and rope key, then the weighted latent sum)."""
    h, kvl, r = cfg["n_heads"], cfg["kv_lora_rank"], cfg["rope_head_dim"]
    attn = 2 * (pos + 1) * h * (2 * kvl + r) * cfg["n_layers"]
    return batch * (2 * token_params(cfg) + attn) + expert_flops(cfg, batch)


def decode_bytes(cfg: dict, batch: int, pos: int) -> float:
    """Bytes one decode step must move: every weight each token meets once
    (norm scales too; of the embedding only the batch's rows), the held
    experts it touches, the latent cache of the pos positions written
    before, and the new position's latent and rope key."""
    d, ql, kvl = cfg["d_model"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    norms = cfg["n_layers"] * (2 * d + ql + kvl) + d
    weights = (token_params(cfg) + norms + batch * d) * BF16
    return (weights + expert_bytes(cfg, batch)
            + batch * (pos + 1) * latent_bytes_per_position(cfg))
