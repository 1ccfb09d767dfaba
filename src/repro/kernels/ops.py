"""jit'd wrappers around the Pallas kernels.

``interpret`` is selected automatically: True on CPU (kernel body runs in
Python for validation), False on TPU (real Mosaic lowering). All public ops
handle padding/reshaping so callers pass natural shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import splash_attention as splash

from repro.kernels.chunk_delta import (changed_mask_pallas,
                                       fingerprint_changed_pallas,
                                       fingerprint_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gmm import expert_gmm, expert_tgmm
from repro.kernels.quantize import (Q4_BLOCK, Q8_BLOCK, dequantize_pallas,
                                    gather_quantize4_pallas,
                                    gather_quantize_pallas, quantize_pallas)
from repro.kernels.ref import (changed_mask_ref, fingerprint_changed_ref,
                               fingerprint_ref, gather_quantize4_ref,
                               gather_quantize_ref, gmm_ref)

CHUNK_WORDS = 1024        # 4 KiB chunks (uint32 words)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _as_u32_blocks(x: jnp.ndarray, chunk_words: int):
    """View any array as [G, chunk_words] uint32 (zero-padded), G % 8 == 0."""
    raw = x.reshape(-1)
    if raw.dtype == jnp.bfloat16 or raw.dtype == jnp.float16:
        raw = raw.view(jnp.uint16).astype(jnp.uint32)
    elif raw.dtype.itemsize == 4:
        raw = raw.view(jnp.uint32)
    elif raw.dtype.itemsize == 8:
        raw = raw.view(jnp.uint32)
    else:
        raw = raw.view(jnp.uint8).astype(jnp.uint32)
    n = raw.shape[0]
    g = -(-n // chunk_words)
    g = -(-g // 8) * 8                     # TILE_G alignment
    pad = g * chunk_words - n
    raw = jnp.pad(raw, (0, pad))
    return raw.reshape(g, chunk_words)


def native_bytes_per_word(dtype) -> int:
    """How many ORIGINAL-array bytes one uint32 word of `_as_u32_blocks`
    output carries. Must mirror the dtype dispatch above: bf16/f16 widen one
    2-byte element per word; 4- and 8-byte dtypes are raw views (4 bytes per
    word); everything else widens one byte per word."""
    name = dtype if isinstance(dtype, str) else str(np.dtype(dtype))
    if name in ("bfloat16", "float16"):
        return 2
    return 4 if np.dtype(name).itemsize in (4, 8) else 1


def _fingerprint(blocks):
    """Backend dispatch: real Mosaic lowering on TPU; on CPU the vectorized
    jnp oracle (bit-identical math, see test_kernels) — per-tile interpret
    mode is orders of magnitude slower and digests never cross processes."""
    if _interpret():
        return fingerprint_ref(blocks)
    return fingerprint_pallas(blocks, interpret=False)


@functools.partial(jax.jit, static_argnames=("chunk_words",))
def fingerprint_leaf(x, chunk_words: int = CHUNK_WORDS):
    """Per-chunk [G,2] uint32 digest of one array (device-side, one pass)."""
    return _fingerprint(_as_u32_blocks(x, chunk_words))


@functools.partial(jax.jit, static_argnames=("chunk_words",))
def fingerprint_and_changed(x, prev_digest, chunk_words: int = CHUNK_WORDS):
    """Fused fingerprint + compare: one pass over the leaf yielding both the
    new [G,2] digests and the int32 [G] changed mask. Use when a previous
    digest exists; first-sight leaves go through ``fingerprint_leaf`` (there
    is nothing to compare against)."""
    blocks = _as_u32_blocks(x, chunk_words)
    if _interpret():
        return fingerprint_changed_ref(blocks, prev_digest)
    return fingerprint_changed_pallas(blocks, prev_digest, interpret=False)


@jax.jit
def changed_chunks(digest, prev_digest):
    """bool-ish int32 [G] mask of chunks whose digest changed."""
    if _interpret():
        return changed_mask_ref(digest, prev_digest).astype(jnp.int32)
    return changed_mask_pallas(digest, prev_digest, interpret=False)


@functools.partial(jax.jit, static_argnames=("chunk_words",))
def gather_changed_rows(x, idx, chunk_words: int = CHUNK_WORDS):
    """[C, W] u32 rows of the block view of `x` selected by `idx` — the only
    device->host payload the delta pipeline transfers per leaf. Deliberately
    a SEPARATE traced computation from the fingerprint: a fused
    digest+blocks pass would write a full padded u32 copy of every leaf per
    checkpoint, even when zero chunks changed; callers skip this entirely
    for frozen leaves (empty idx)."""
    return jnp.take(_as_u32_blocks(x, chunk_words), idx, axis=0)


def quantizable_dtype(dtype) -> bool:
    """True for dtypes the fused q8 path supports. Restricted to the float
    dtypes whose `_as_u32_blocks` view carries exactly one element per u32
    word — so the float chunk rows below align 1:1 with fingerprint chunks
    and a changed-row index means the same thing in both views."""
    name = dtype if isinstance(dtype, str) else str(np.dtype(dtype))
    return name in ("float32", "bfloat16", "float16")


@functools.partial(jax.jit, static_argnames=("chunk_words", "block"))
def gather_quantize_blocks(x, idx, chunk_words: int = CHUNK_WORDS,
                           block: int = Q8_BLOCK):
    """Fused gather + blockwise-int8 quantize of the CHANGED chunk rows of a
    float leaf: (q int8 [C, W], scales f32 [C, W // block]). Rows are the
    leaf's [G, chunk_words]-element f32 chunk view (same row indexing as the
    fingerprint view for quantizable dtypes); only rows named by ``idx`` are
    read — the wire-format payload leaves the device in one pass."""
    block = min(block, chunk_words)            # small-chunk configs
    blocks = _padded_float_blocks(x, chunk_words)
    if _interpret():
        return gather_quantize_ref(blocks, idx, block)
    return gather_quantize_pallas(blocks, idx, block=block, interpret=False)


def _padded_float_blocks(x, chunk_words: int):
    """The leaf's [g, chunk_words] f32 chunk view, g TILE_G-aligned — the
    shared row layout of every fused gather variant."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    g = -(-n // chunk_words)
    g = -(-g // 8) * 8
    flat = jnp.pad(flat, (0, g * chunk_words - n))
    return flat.reshape(g, chunk_words)


@functools.partial(jax.jit, static_argnames=("chunk_words", "block"))
def gather_quantize4_blocks(x, idx, chunk_words: int = CHUNK_WORDS,
                            block: int = Q4_BLOCK):
    """Fused gather + blockwise-int4 quantize of the CHANGED chunk rows of a
    float leaf: (packed uint8 [C, chunk_words // 2], scales f32
    [C, chunk_words // block]). Two elements per byte in the half-split
    nibble layout; per-element error bounded by half a quantization step
    (block absmax / 14)."""
    block = min(block, chunk_words)            # small-chunk configs
    blocks = _padded_float_blocks(x, chunk_words)
    if _interpret():
        return gather_quantize4_ref(blocks, idx, block)
    return gather_quantize4_pallas(blocks, idx, block=block, interpret=False)


@functools.partial(jax.jit, static_argnames=("chunk_words",))
def chunk_absmax(x, chunk_words: int = CHUNK_WORDS):
    """Per-chunk-row f32 absmax of a float leaf ([g] over the same padded
    row layout the fused gathers use). The encoding selector turns this into
    a GUARANTEED per-chunk error bound (q4 half-step = a/14, q8 = a/254;
    the selector tests a/13.5 and a/126 to absorb f32 scale rounding), so
    the cheapest encoding satisfying the slot's atol is chosen per chunk
    before any gather runs."""
    return jnp.max(jnp.abs(_padded_float_blocks(x, chunk_words)), axis=1)


# ------------------------------------------------------------- q8 wire codec
# Self-describing quantized chunk payload (little-endian):
#   [u32 n_elems][u32 block][f32 scales[ceil(n_elems/block)]][int8 q[n_elems]]
# The store writes these bytes as the chunk body (enc="q8"); restore
# dequantizes transparently via `q8_decode_chunk`.

def q8_encode_chunk(q_row: np.ndarray, scales: np.ndarray, n_elems: int,
                    block: int = Q8_BLOCK) -> bytes:
    """Pack one quantized chunk row (int8 [W], f32 [W // block]) into the
    q8 wire format, trimming to the chunk's real `n_elems` (the last chunk
    of a leaf is usually partial)."""
    n_sub = -(-n_elems // block)
    head = np.uint32(n_elems).tobytes() + np.uint32(block).tobytes()
    return (head
            + np.ascontiguousarray(scales[:n_sub], np.float32).tobytes()
            + np.ascontiguousarray(q_row[:n_elems], np.int8).tobytes())


def q8_decode_chunk(payload: bytes, dtype) -> bytes:
    """Dequantize one q8 chunk payload back to the leaf's native bytes."""
    n = int(np.frombuffer(payload[:4], np.uint32)[0])
    block = int(np.frombuffer(payload[4:8], np.uint32)[0])
    n_sub = -(-n // block)
    scales = np.frombuffer(payload[8:8 + 4 * n_sub], np.float32)
    q = np.frombuffer(payload[8 + 4 * n_sub:8 + 4 * n_sub + n], np.int8)
    pad = (-n) % block
    qf = np.pad(q.astype(np.float32), (0, pad)).reshape(n_sub, block)
    x = (qf * scales[:, None]).reshape(-1)[:n]
    # bf16 is registered with numpy via ml_dtypes (a jax dependency), so a
    # plain astype covers f32/bf16/f16 alike
    out = x.astype(jnp.dtype(dtype) if isinstance(dtype, str) else dtype)
    return np.ascontiguousarray(out).tobytes()


# ------------------------------------------------------------- q4 wire codec
# Self-describing int4 chunk payload (little-endian):
#   [u32 n_elems][u32 block][f32 scales[W/block]][u8 packed[W/2]]
# scales and packed bytes cover the FULL kernel row W (untrimmed; W is
# recovered from the payload length: bytes after the 8-byte header =
# n_sub * (4 + block/2), so n_sub = after / (4 + block//2), W = n_sub*block).
# Nibbles use the half-split layout: byte j holds element j (low) and
# element j + W/2 (high), signed two's-complement in 4 bits.

def q4_encode_chunk(packed_row: np.ndarray, scales: np.ndarray,
                    n_elems: int, block: int = Q4_BLOCK) -> bytes:
    """Pack one int4-quantized chunk row (uint8 [W // 2], f32 [W // block])
    into the q4 wire format. The packed row is kept whole — the half-split
    nibble layout interleaves elements W/2 apart, so a partial last chunk
    cannot trim bytes the way q8 does; `n_elems` in the header trims on
    decode instead."""
    head = np.uint32(n_elems).tobytes() + np.uint32(block).tobytes()
    return (head
            + np.ascontiguousarray(scales, np.float32).tobytes()
            + np.ascontiguousarray(packed_row, np.uint8).tobytes())


def q4_decode_chunk(payload: bytes, dtype) -> bytes:
    """Dequantize one q4 chunk payload back to the leaf's native bytes."""
    n = int(np.frombuffer(payload[:4], np.uint32)[0])
    block = int(np.frombuffer(payload[4:8], np.uint32)[0])
    after = len(payload) - 8
    n_sub = after // (4 + block // 2)
    W = n_sub * block
    scales = np.frombuffer(payload[8:8 + 4 * n_sub], np.float32)
    packed = np.frombuffer(payload[8 + 4 * n_sub:], np.uint8)
    q = np.empty(W, np.int8)
    lo = (packed & 0xF).astype(np.int8)
    hi = (packed >> 4).astype(np.int8)
    q[: W // 2] = lo - ((lo > 7) << 4)       # sign-extend 4 -> 8 bits
    q[W // 2:] = hi - ((hi > 7) << 4)
    qf = q.astype(np.float32).reshape(n_sub, block)
    x = (qf * scales[:, None]).reshape(-1)[:n]
    out = x.astype(jnp.dtype(dtype) if isinstance(dtype, str) else dtype)
    return np.ascontiguousarray(out).tobytes()


# -------------------------------------------------------- decode dispatch --
def decode_wire_chunk(payload: bytes, enc: str, dtype) -> bytes:
    """Decode one stored chunk body to native leaf bytes given its manifest
    ``enc`` marker. Handles every wire encoding ("raw", "q8", "q4") plus the
    "+z" entropy-stage suffix (byte-plane-shuffled compression applied on
    the writer thread; see parallel/compression.py)."""
    if enc.endswith("+z"):
        from repro.parallel.compression import entropy_decode_bytes
        payload = entropy_decode_bytes(payload)
        enc = enc[:-2]
    if enc == "q8":
        return q8_decode_chunk(payload, dtype)
    if enc == "q4":
        return q4_decode_chunk(payload, dtype)
    return payload


@functools.partial(jax.jit, static_argnames=("block",))
def quantize_blocks(x, block: int = 256):
    """Flat blockwise int8 quantization: returns (q [G,block], scale [G],
    n) for any input shape; G padded to the kernel tile."""
    flat = x.reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    g = -(-n // block)
    g = -(-g // 8) * 8
    flat = jnp.pad(flat, (0, g * block - n))
    q, scale = quantize_pallas(flat.reshape(g, block), interpret=_interpret())
    return q, scale


def dequantize_blocks(q, scale, shape, dtype):
    x = dequantize_pallas(q, scale, interpret=_interpret())
    n = int(np.prod(shape))
    return x.reshape(-1)[:n].reshape(shape).astype(dtype)


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    block_q: int = 128, block_k: int = 128):
    return flash_attention_pallas(q, k, v, causal=causal, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  interpret=_interpret())


# ------------------------------------------------------ expert products ---

def expert_matmul(lhs, rhs, tile_group, num_tiles, tm: int):
    """Grouped product of a tile-aligned expert layout (``kernels/gmm.py``):
    ``out[r] = lhs[r] @ rhs[g(r)]``, differentiable in ``lhs`` and ``rhs``.
    On TPU the forward and the input gradient are ``expert_gmm`` and the
    weight gradient ``expert_tgmm``; on CPU the jnp oracle, differentiated
    by JAX."""
    if _interpret():
        return gmm_ref(lhs, rhs, tile_group, num_tiles, tm)
    return expert_matmul_pallas(lhs, rhs, tile_group, num_tiles, tm, False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def expert_matmul_pallas(lhs, rhs, tile_group, num_tiles, tm, interpret):
    return expert_gmm(lhs, rhs, tile_group, num_tiles, tm=tm,
                      interpret=interpret)


def _expert_matmul_fwd(lhs, rhs, tile_group, num_tiles, tm, interpret):
    out = expert_matmul_pallas(lhs, rhs, tile_group, num_tiles, tm,
                               interpret)
    return out, (lhs, rhs, tile_group, num_tiles)


def _expert_matmul_bwd(tm, interpret, res, g):
    lhs, rhs, tile_group, num_tiles = res
    G = rhs.shape[0]
    dlhs = expert_gmm(g, rhs, tile_group, num_tiles, tm=tm,
                      transpose_rhs=True, interpret=interpret)
    active = jnp.arange(tile_group.shape[0]) < num_tiles
    tiles = jnp.zeros((G,), jnp.int32).at[tile_group].add(
        active.astype(jnp.int32))
    drhs = expert_tgmm(lhs, g, tile_group, num_tiles, tiles, num_groups=G,
                       tm=tm, interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None, None


expert_matmul_pallas.defvjp(_expert_matmul_fwd, _expert_matmul_bwd)


# ----------------------------------------------------- causal attention ---

# q and kv block of the splash kernels, forward and backward: the first that
# divides the sequence. On a TPU v5e at B 2, H 16, S 8192, qk 192, v 128 in
# bfloat16, larger blocks ran faster in the forward and in both backward
# kernels (forward 10.0 ms at 512, 20.0 at 256, 59.8 at 128)
ATTENTION_BLOCKS = (512, 256, 128)


def attention_block(seq: int):
    """The square block ``causal_attention`` tiles a sequence of ``seq``
    into, forward and backward; None where no candidate divides it."""
    return next((b for b in ATTENTION_BLOCKS if seq % b == 0), None)


@functools.lru_cache(maxsize=16)
def _splash_causal(heads: int, seq: int, block: int, interpret: bool):
    """The splash MHA kernel over ``heads`` causal [seq, seq] masks. Its mask
    tables are built in numpy; evaluated eagerly, so that a kernel first
    made inside a trace holds no tracer."""
    mask = splash.MultiHeadMask([splash.CausalMask((seq, seq))] * heads)
    blocks = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                      q_seq_shards=1, interpret=interpret)


def causal_attention(q, k, v, *, block=None):
    """Causal softmax attention at scale ``1/sqrt(dqk)``: q, k [B, H, S, dqk]
    and v [B, H, S, dv] give [B, H, S, dv]. The splash kernels (forward, dq,
    dkv under their own VJP) skip the wholly masked blocks; the forward's
    softmax statistics and PV are float32, and QK^T accumulates in float32
    from the inputs' dtype. ``block`` defaults to ``attention_block(S)``."""
    B, H, S, dqk = q.shape
    kernel = _splash_causal(H, S, block or attention_block(S), _interpret())
    # the kernel takes no scale: fold it into q, rounded once from float32
    q = (q.astype(jnp.float32) / np.sqrt(dqk)).astype(q.dtype)
    return jax.vmap(kernel)(q, k, v)
