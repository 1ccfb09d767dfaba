"""Moonlight-16B-A3B (moonshotai; ``model_type`` deepseek_v3) — MLA with a
direct query projection, one leading dense layer, then 26 layers of 64
routed experts (top-6, sigmoid scores with a selection bias, normalised
gates scaled by 2.446) beside 2 shared experts.

Source: https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json
(``n_group`` = ``topk_group`` = 1, so no group limit on the selection).
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,                 # the leading dense layer
    vocab_size=163840,
    ffn_activation="swiglu",
    rope_theta=50000.0,
    norm_eps=1e-5,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        d_ff_expert=1408,
        num_shared_experts=2,
        first_dense_layers=1,
        router="sigmoid",
        selection_bias=True,
        routed_scaling=2.446,
    ),
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
)

# one chip's share of an 8-way expert-parallel layer, at smoke widths
SMOKE = CONFIG.replace(
    name="moonlight-16b-a3b-smoke",
    num_layers=3,               # 1 dense + 2 moe
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=128,
    vocab_size=256,
    moe=MoEConfig(
        num_experts=16,
        top_k=4,
        d_ff_expert=32,
        num_shared_experts=2,
        first_dense_layers=1,
        router="sigmoid",
        selection_bias=True,
        routed_scaling=2.446,
        expert_offset=4,
        experts_held=8,
    ),
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
    ),
)
