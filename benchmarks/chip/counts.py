"""Operations and bytes that the model requires, from configuration shapes.

These count the work of the model, never what an implementation happens to
do: no recomputation, no reads of cache positions that hold nothing yet,
no padding. ``cfg`` is a configuration file's ``model_config``.

A decode step of a dense GQA decoder at position ``pos`` (0-based) feeds
one token per request through every layer; the token attends to the
``pos + 1`` positions up to and including its own.
"""
from __future__ import annotations

BF16 = 2


def layer_matmul_params(cfg: dict) -> int:
    d, h, kv, hd, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["head_dim"], cfg["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def matmul_params(cfg: dict) -> int:
    """Weights that multiply each token: every layer's projections and FFN,
    and the head."""
    return cfg["n_layers"] * layer_matmul_params(cfg) + cfg["d_model"] * cfg["vocab_size"]


def kv_bytes_per_position(cfg: dict) -> int:
    """Key and value bytes of one position of one request, all layers."""
    return cfg["n_layers"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"] * BF16


def decode_flops(cfg: dict, batch: int, pos: int) -> int:
    """Operations of one decode step: two per multiply-add of the weights,
    and q.k plus p.v over the pos + 1 attended positions in every layer."""
    attn = 4 * (pos + 1) * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    return batch * (2 * matmul_params(cfg) + attn)


def decode_bytes(cfg: dict, batch: int, pos: int) -> int:
    """Bytes one decode step must move: every weight once (norm scales too;
    of an untied embedding only the batch's rows), the cache of the pos
    positions written before, and the new position's keys and values."""
    d = cfg["d_model"]
    norms = (2 * cfg["n_layers"] + 1) * d
    emb = 0 if cfg["tie_embeddings"] else batch * d
    weights = (matmul_params(cfg) + norms + emb) * BF16
    return weights + batch * (pos + 1) * kv_bytes_per_position(cfg)
