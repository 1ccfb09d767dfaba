"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FP_PRIME1 = np.uint32(2654435761)
FP_PRIME2 = np.uint32(2246822519)
FP_PRIME3 = np.uint32(3266489917)


def fingerprint_ref(x_u32: jnp.ndarray) -> jnp.ndarray:
    """Per-row fingerprint of a [G, B] uint32 view. Returns [G, 2] uint32.
    Position-mixed so permutations change the digest."""
    G, B = x_u32.shape
    pos = (jnp.arange(B, dtype=jnp.uint32) * FP_PRIME1)[None, :]
    v = (x_u32 ^ pos) * FP_PRIME2
    d0 = jax.lax.reduce(v, np.uint32(0), jax.lax.bitwise_xor, (1,))
    d1 = jnp.sum(v * FP_PRIME3, axis=1, dtype=jnp.uint32)
    return jnp.stack([d0, d1], axis=1)


def changed_mask_ref(digest: jnp.ndarray, prev: jnp.ndarray) -> jnp.ndarray:
    """[G,2] x [G,2] -> bool [G]; True where the chunk changed."""
    return jnp.any(digest != prev, axis=1)


def fingerprint_changed_ref(x_u32: jnp.ndarray, prev: jnp.ndarray):
    """Fused-kernel oracle: ([G,2] digests, int32 [G] changed mask)."""
    d = fingerprint_ref(x_u32)
    return d, changed_mask_ref(d, prev).astype(jnp.int32)


def gather_quantize_ref(x: jnp.ndarray, idx: jnp.ndarray, block: int = 256):
    """Fused gather+quantize oracle over the [G, W] float chunk view:
    returns (q int8 [C, W], scales f32 [C, W // block])."""
    rows = jnp.take(x.astype(jnp.float32), idx, axis=0)
    C, W = rows.shape
    q, s = quantize_ref(rows.reshape(C * (W // block), block))
    return q.reshape(C, W), s.reshape(C, W // block)


def gather_quantize4_ref(x: jnp.ndarray, idx: jnp.ndarray, block: int = 256):
    """Fused gather+int4-quantize oracle over the [G, W] float chunk view:
    returns (packed uint8 [C, W // 2], scales f32 [C, W // block]) with the
    half-split nibble layout (element j in the low nibble of byte j, element
    j + W/2 in its high nibble)."""
    rows = jnp.take(x.astype(jnp.float32), idx, axis=0)
    C, W = rows.shape
    sub = rows.reshape(C * (W // block), block)
    scale = jnp.maximum(jnp.max(jnp.abs(sub), axis=1) / 7.0, 1e-12)
    q = jnp.clip(jnp.round(sub / scale[:, None]), -7, 7).astype(jnp.int32)
    q = q.reshape(C, W)
    lo = q[:, : W // 2] & 0xF
    hi = q[:, W // 2:] & 0xF
    return ((lo | (hi << 4)).astype(jnp.uint8),
            scale.reshape(C, W // block).astype(jnp.float32))


def quantize_ref(x: jnp.ndarray):
    """Blockwise int8 quantization of [G, B] f32. Returns (q int8 [G,B],
    scale f32 [G])."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_ref(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale[:, None]


def flash_attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """q [B,H,Sq,d], k/v [B,KV,Sk,d] with H % KV == 0. f32 softmax."""
    B, H, Sq, d = q.shape
    KV = k.shape[1]
    G = H // KV
    qg = q.reshape(B, KV, G, Sq, d).astype(jnp.float32)
    s = jnp.einsum("bkgqd,bksd->bkgqs", qg, k.astype(jnp.float32))
    s = s * (scale if scale is not None else 1.0 / np.sqrt(d))
    if causal:
        Sk = k.shape[2]
        mask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None] + (Sk - Sq)
        s = jnp.where(mask, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", w, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, d).astype(q.dtype)


def _row_groups(tile_group, num_tiles, tm: int, rows: int):
    """Group of each row of a tile-aligned expert layout, -1 past the last
    tile that holds rows."""
    g = jnp.repeat(tile_group, tm, total_repeat_length=rows)
    return jnp.where(jnp.arange(rows) < num_tiles * tm, g, -1)


def gmm_ref(lhs, rhs, tile_group, num_tiles, tm: int,
            transpose_rhs: bool = False):
    """Grouped-matmul oracle: ``out[r] = lhs[r] @ rhs[g(r)]`` (``.T`` with
    ``transpose_rhs``) for rows in the first ``num_tiles`` tiles, 0 after;
    one masked product per group."""
    g = _row_groups(tile_group, num_tiles, tm, lhs.shape[0])
    spec = "mk,nk->mn" if transpose_rhs else "mk,kn->mn"
    out = jnp.zeros((lhs.shape[0], rhs.shape[1] if transpose_rhs
                     else rhs.shape[2]), jnp.float32)
    for e in range(rhs.shape[0]):
        part = jnp.einsum(spec, lhs, rhs[e], preferred_element_type=jnp.float32)
        out = out + jnp.where((g == e)[:, None], part, 0.0)
    return out.astype(lhs.dtype)


def tgmm_ref(lhs, dout, tile_group, num_tiles, tm: int, num_groups: int):
    """Weight-gradient oracle: ``out[e] = lhs[rows of e].T @ dout[rows of
    e]``, [num_groups, K, N]."""
    g = _row_groups(tile_group, num_tiles, tm, lhs.shape[0])
    hot = (g[:, None] == jnp.arange(num_groups)[None, :]).astype(jnp.float32)
    return jnp.einsum("mk,me,mn->ekn", lhs.astype(jnp.float32), hot,
                      dout.astype(jnp.float32)).astype(lhs.dtype)
