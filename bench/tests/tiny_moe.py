"""A smoke-size MLA and mixture-of-experts cell for rehearsals off the chip:
Moonlight's layout (a dense layer, then layers of sigmoid-routed experts
beside shared ones, a direct query projection) at widths a CPU test can
hold, holding 8 of 16 experts from the fifth on."""
from harness.spec import Cell

TINY_MOE_CONFIG = {
    "name": "tiny-mla-moe", "model_type": "deepseek_v3",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "n_routed_experts_in_layer": 16,
    "held_expert_offset": 4, "num_experts_per_tok": 4, "n_shared_experts": 2,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "vocab_size": 300, "hidden_act": "silu", "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "tie_word_embeddings": False,
    "attention_bias": False, "reference": "mla_moe",
}


def tiny_moe_cell(trainable=None, limits=None):
    traffic = {"batch": 4, "seq": 32, "steps_per_ckpt": 2,
               "trainable": trainable or {"experts_per_layer": 1},
               "await_full": True, "max_warmup_intervals": 5,
               "reference_block_rows": 2}
    limits = limits or {"loss_gap": 2e-4, "grad_gap": 8e-3,
                        "update_gap": 3e-3, "store_mismatch": 0}
    e2e = [{"name": "record_tokens_per_s", "unit": "tokens/s"},
           {"name": "stored_mb_per_ckpt", "unit": "MB"},
           {"name": "setup_s", "unit": "s"}]
    return Cell("tiny_moe.record", TINY_MOE_CONFIG, traffic, limits, 1, e2e,
                [])
