"""Operation and byte counts against counts made by hand at a small size."""
from __future__ import annotations

import pytest

import bench_helpers  # noqa: F401  (puts the paths in place)
import counts

# d 64, 4 heads and 2 KV heads of 16, d_ff 128, vocab 256, 2 layers
CFG = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
           d_ff=128, vocab_size=256, tie_embeddings=True)


def test_matmul_params_by_hand():
    # q 64*64 + k,v 2*64*32 + o 64*64 = 12288; ffn 3*64*128 = 24576
    assert counts.layer_matmul_params(CFG) == 12288 + 24576
    assert counts.matmul_params(CFG) == 2 * 36864 + 64 * 256


def test_kv_bytes_per_position_by_hand():
    assert counts.kv_bytes_per_position(CFG) == 2 * 2 * 2 * 16 * 2


@pytest.mark.parametrize("pos", [0, 9])
def test_decode_flops_by_hand(pos):
    # 2 per weight per token; q.k and p.v: 2 * 2 * (pos + 1) * 4 heads * 16
    per_token = 2 * 90112 + 2 * (4 * (pos + 1) * 4 * 16)
    assert counts.decode_flops(CFG, 3, pos) == 3 * per_token


@pytest.mark.parametrize("tied,extra", [(True, 0), (False, 3 * 64)])
def test_decode_bytes_by_hand(tied, extra):
    cfg = dict(CFG, tie_embeddings=tied)
    weights = (90112 + 5 * 64 + extra) * 2       # matmuls, 5 norm scales, rows
    cache = 3 * (9 + 1) * 256                    # 3 requests, 10 positions
    assert counts.decode_bytes(cfg, 3, 9) == weights + cache
