"""A smoke-size cell for rehearsals off the chip: the same harness, model
family and record path as the real cells, at widths a CPU test can hold."""
from harness.spec import Cell

TINY_CONFIG = {
    "name": "tiny-dense", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 3, "vocab_size": 300, "hidden_act": "silu",
    "rms_norm_eps": 1e-5, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "reference": "dense_lm",
}


def tiny_cell(trainable="all", steps_per_ckpt=2, limits=None,
              max_warmup=5, config=TINY_CONFIG):
    # awaiting the full checkpoint makes the warm-up steady however fast
    # this machine's writer runs beside the tiny steps
    traffic = {"batch": 4, "seq": 32, "steps_per_ckpt": steps_per_ckpt,
               "trainable": trainable, "await_full": True,
               "max_warmup_intervals": max_warmup,
               "reference_block_rows": 2}
    # set from this cell's program and control readings on the CPU (seeds
    # 5, 6 and 2**31 + 9): program at most 8.6e-5 / 4.4e-3 / 1.1e-3,
    # float8 control at least 3.7e-4 / 1.1e-2 / 5.0e-3
    limits = limits or {"loss_gap": 2e-4, "grad_gap": 8e-3,
                        "update_gap": 3e-3, "store_mismatch": 0}
    e2e = [{"name": "record_tokens_per_s", "unit": "tokens/s"},
           {"name": "stored_mb_per_ckpt", "unit": "MB"},
           {"name": "setup_s", "unit": "s"}]
    return Cell("tiny.record", config, traffic, limits, 1, e2e, [])
