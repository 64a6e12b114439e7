"""DeepSeek-V2 236B: MLA (kv_lora=512) + 2 shared / 160 routed top-6 MoE
with group-limited greedy routing (top 3 of 8 groups), YaRN rope
[arXiv:2405.04434; hf]. All experts held: a chip's share of an
expert-parallel deployment sets ``experts_first``/``experts_held``."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    family="moe",
    n_layers=60,
    d_model=5120,
    n_heads=128,
    n_kv_heads=128,       # MLA: per-head K/V reconstructed from the latent
    d_ff=1536,
    moe_d_ff=1536,
    dense_d_ff=12288,
    first_k_dense=1,
    vocab_size=102400,
    head_dim=128,
    n_experts=160,
    n_shared_experts=2,
    top_k=6,
    n_group=8,
    topk_group=3,
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    use_mla=True,
    kv_lora_rank=512,
    q_lora_rank=1536,
    rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10_000.0,
    yarn_factor=40.0,
    yarn_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    norm_eps=1e-6,
)
