"""The work an algorithm must do, from shapes alone: training FLOPs of a
dense decoder step, and the bytes the fused record pass must move. Neither
depends on how the program implements them, so a roofline share reads the
same work whatever the implementation."""
from __future__ import annotations

import math

import numpy as np

# the record pass's chunk: 16384 words of the fingerprint view, one word per
# 2-byte float and 4 native bytes per word otherwise
CHUNK_WORDS = 16384
DIGEST_BYTES = 8            # two uint32 words per chunk
MASK_BYTES = 4              # one int32 per chunk


def _layer_matmul_flops(d: dict) -> float:
    """Forward matmul FLOPs of one layer per token (projections + MLP)."""
    dm, h, kv, hd, ff = (d["d_model"], d["num_heads"], d["num_kv_heads"],
                         d["head_dim"], d["d_ff"])
    proj = dm * h * hd + 2 * dm * kv * hd + h * hd * dm
    mlp = dm * ff * (3 if d["gated"] else 2)
    return 2.0 * (proj + mlp)


def step_flops(d: dict, batch: int, seq: int, top_layers=None) -> float:
    """Model FLOPs one training step requires: the forward over every layer
    and the logits, and the backward only where gradients are needed.
    Causal attention counts the keys each query attends, ``(seq + 1) / 2``
    on average; recomputation under remat is not counted.

    ``top_layers`` None is full training (backward = twice the forward,
    everywhere). Otherwise only the top ``top_layers`` layers and the final
    norm train: the backward runs through the logits (input gradient only,
    the tied embedding is frozen) and those layers, and the lowest of them
    needs no gradient for its input projections' input."""
    L, h, hd, V = d["num_layers"], d["num_heads"], d["head_dim"], d["vocab_size"]
    tokens = batch * seq
    preds = batch * (seq - 1)                  # positions with a next token
    layer = _layer_matmul_flops(d) * tokens
    attn = 4.0 * h * hd * (seq + 1) / 2 * tokens      # QK^T and PV
    logits = 2.0 * d["d_model"] * V * preds
    fwd = L * (layer + attn) + logits
    if top_layers is None:
        return 3.0 * fwd
    qkv_input_grad = 2.0 * d["d_model"] * (h + 2 * d["num_kv_heads"]) * hd \
        * tokens
    bwd = logits + top_layers * 2.0 * (layer + attn) - qkv_input_grad
    return fwd + bwd


def _bytes_per_word(dtype) -> int:
    dt = np.dtype(dtype)
    if dt.name in ("bfloat16", "float16"):
        return 2
    return 4 if dt.itemsize in (4, 8) else 1


def fingerprint_bytes(leaves) -> float:
    """HBM bytes the fused fingerprint+compare pass must move over
    ``leaves`` (``(shape, dtype)`` pairs): each leaf's native bytes read
    once, the previous digests read, the new digests and the changed mask
    written — one digest and one mask entry per chunk."""
    total = 0.0
    for shape, dtype in leaves:
        nbytes = math.prod(shape) * np.dtype(dtype).itemsize
        if nbytes == 0:
            continue
        chunks = -(-nbytes // (CHUNK_WORDS * _bytes_per_word(dtype)))
        total += nbytes + chunks * (2 * DIGEST_BYTES + MASK_BYTES)
    return total
