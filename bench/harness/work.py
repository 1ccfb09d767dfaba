"""The work the fused record pass must do, from shapes alone: the bytes it
must move. It does not depend on how the program implements the pass, so a
roofline share reads the same work whatever the implementation. (A step's
FLOPs depend on the model: each family module gives its ``step_flops``.)"""
from __future__ import annotations

import math

import numpy as np

# the record pass's chunk: 16384 words of the fingerprint view, one word per
# 2-byte float and 4 native bytes per word otherwise
CHUNK_WORDS = 16384
DIGEST_BYTES = 8            # two uint32 words per chunk
MASK_BYTES = 4              # one int32 per chunk


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
