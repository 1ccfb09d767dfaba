"""The FLOP and byte functions at shapes worked out by hand."""
import ml_dtypes
import numpy as np

from harness import work
from harness.spec import load_module

dense = load_module("families", "dense_lm")

# one layer, d 4, 2 heads of 2, 1 kv head, gated d_ff 8, vocab 10
DIMS = {"d_model": 4, "num_layers": 1, "num_heads": 2, "num_kv_heads": 1,
        "head_dim": 2, "d_ff": 8, "vocab_size": 10, "gated": True}


def test_full_training_flops_by_hand():
    b, s = 3, 5
    tokens, preds = b * s, b * (s - 1)
    proj = 4 * 2 * 2 + 2 * 4 * 1 * 2 + 2 * 2 * 4        # q, k+v, o
    mlp = 4 * 8 * 3
    layer = 2 * (proj + mlp) * tokens
    attn = 4 * 2 * 2 * (s + 1) / 2 * tokens
    logits = 2 * 4 * 10 * preds
    assert dense.step_flops(DIMS, b, s, "all") == 3 * (layer + attn + logits)


def test_frozen_training_flops_by_hand():
    d = dict(DIMS, num_layers=3)
    b, s = 2, 4
    tokens = b * s
    layer = 2 * (4 * 2 * 2 + 2 * 4 * 2 + 2 * 2 * 4 + 4 * 8 * 3) * tokens
    attn = 4 * 2 * 2 * (s + 1) / 2 * tokens
    logits = 2 * 4 * 10 * b * (s - 1)
    fwd = 3 * (layer + attn) + logits
    qkv_in = 2 * 4 * (2 + 2) * 2 * tokens
    bwd = logits + 2 * (layer + attn) - qkv_in
    top = {"top_layers": 1}
    assert dense.step_flops(d, b, s, top) == fwd + bwd
    assert dense.step_flops(d, b, s, top) < dense.step_flops(d, b, s, "all")


def test_florbench_step_is_about_23_tflop():
    import json
    from harness.spec import BENCH
    cfg = json.loads((BENCH / "configs" / "florbench-100m.json").read_text())
    f = dense.step_flops(dense.dims(cfg), 32, 1024, "all")
    # 6 x 85.0M x 32768 + logits 4.94e12 + causal attention 1.86e12
    assert 23.4e12 < f < 23.6e12


def test_fingerprint_bytes_by_hand():
    # 3 x 65536 f32 = 768 KiB = 12 chunks of 64 KiB; a bf16 leaf of 40000
    # elements = 80000 bytes in chunks of 32 KiB (3); a scalar, 1 chunk
    leaves = [((3, 65536), np.float32), ((40000,), ml_dtypes.bfloat16),
              ((), np.int32), ((0, 5), np.float32)]
    per_chunk = 2 * 8 + 4
    want = (3 * 65536 * 4 + 12 * per_chunk) + (80000 + 3 * per_chunk) \
        + (4 + per_chunk)
    assert work.fingerprint_bytes(leaves) == want
