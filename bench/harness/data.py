"""Training tokens from (step, seed): a copy of the program's
``repro.data.synthetic_batch`` for token-only decoders, kept here so that no
change to the program can change the traffic.

A splitmix64 counter hash gives every row of every step its own tokens,
with a Zipf-like skew so the loss moves.
"""
from __future__ import annotations

import numpy as np

_MASK64 = (1 << 64) - 1


def _splitmix64(x: np.ndarray) -> np.ndarray:
    x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
    z = x
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _counters(step: int, seed: int, n: int) -> np.ndarray:
    base = np.uint64(((seed << 32) ^ step) & _MASK64)
    return _splitmix64(base + np.arange(n, dtype=np.uint64))


def tokens(step: int, seed: int, batch: int, seq: int,
           vocab: int) -> np.ndarray:
    """[batch, seq] int32 token ids of one training step."""
    r = _counters(step, seed, batch * seq)
    u = (r >> np.uint64(11)).astype(np.float64) / float(1 << 53)
    toks = np.floor(vocab * np.power(u, 3.0)).astype(np.int64)
    return np.clip(toks, 0, vocab - 1).astype(np.int32).reshape(batch, seq)
