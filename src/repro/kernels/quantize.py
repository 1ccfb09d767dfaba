"""Pallas TPU kernels: blockwise int8 quantize/dequantize (+ fused gather).

Backs three subsystems: checkpoint compression (optimizer moments tolerate
blockwise int8; error-bounded), the cross-pod gradient-compression codec
(parallel/compression.py), and the fused checkpoint fast path
(``gather_quantize_pallas``: changed chunk rows leave the device already
wire-format, via scalar-prefetch gather + quantize in one VMEM pass). The
two record-path gathers are named ``gather_quantize8`` and
``gather_quantize4`` in a profiler trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_G = 8
Q8_BLOCK = 256
Q4_BLOCK = 256


def _quant_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)               # [TILE_G, B]
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    scale_ref[...] = scale.astype(jnp.float32)


def quantize_pallas(x: jnp.ndarray, *, interpret: bool = True,
                    tile_g: int = TILE_G):
    """[G, B] float -> (q int8 [G, B], scale f32 [G])."""
    G, B = x.shape
    assert G % tile_g == 0, (G, tile_g)
    return pl.pallas_call(
        _quant_kernel,
        grid=(G // tile_g,),
        in_specs=[pl.BlockSpec((tile_g, B), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile_g, B), lambda i: (i, 0)),
                   pl.BlockSpec((tile_g,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((G, B), jnp.int8),
                   jax.ShapeDtypeStruct((G,), jnp.float32)],
        interpret=interpret,
    )(x)


def _gather_quant_kernel(idx_ref, x_ref, q_ref, scale_ref):
    del idx_ref  # consumed by the BlockSpec index_map, not the body
    sub = x_ref[...].astype(jnp.float32)             # [W // block, block]
    scale = jnp.maximum(jnp.max(jnp.abs(sub), axis=1, keepdims=True) / 127.0,
                        1e-12)
    q_ref[...] = jnp.clip(jnp.round(sub / scale), -127, 127).astype(jnp.int8)
    scale_ref[...] = scale


def gather_quantize_pallas(x: jnp.ndarray, idx: jnp.ndarray, *,
                           block: int = Q8_BLOCK, interpret: bool = True):
    """Fused gather + blockwise-int8 quantize over CHANGED chunk rows.

    ``x`` is the [G, W] float chunk view of a leaf, ``idx`` the int32 [C]
    changed-row indices. The grid runs one program per changed row; the row
    index is scalar-prefetched so the BlockSpec index_map DMAs only the
    selected rows into VMEM — frozen rows are never read. Each row arrives
    as a squeezed [W // block, block] tile (one sub-block per sublane) and
    is quantized per sub-block (same codec layout as
    parallel/compression.py). Returns (q int8 [C, W], scales f32
    [C, W // block])."""
    G, W = x.shape
    C = int(idx.shape[0])
    assert W % block == 0, (W, block)
    n_sub = W // block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=[pl.BlockSpec((None, n_sub, block),
                               lambda i, idx_ref: (idx_ref[i], 0, 0))],
        out_specs=[pl.BlockSpec((None, n_sub, block),
                                lambda i, idx_ref: (i, 0, 0)),
                   pl.BlockSpec((None, n_sub, 1),
                                lambda i, idx_ref: (i, 0, 0))],
    )
    q, scale = pl.pallas_call(
        _gather_quant_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((C, n_sub, block), jnp.int8),
                   jax.ShapeDtypeStruct((C, n_sub, 1), jnp.float32)],
        interpret=interpret,
        name="gather_quantize8",
    )(idx, x.reshape(G, n_sub, block))
    return q.reshape(C, W), scale.reshape(C, n_sub)


def _gather_quant4_kernel(idx_ref, x_ref, p_ref, scale_ref, *, shared: bool):
    del idx_ref  # consumed by the BlockSpec index_map, not the body
    lo = x_ref[0].astype(jnp.float32)                # [m, L] first half
    hi = x_ref[1].astype(jnp.float32)                # [m, L] second half
    a_lo = jnp.max(jnp.abs(lo), axis=1, keepdims=True)
    a_hi = jnp.max(jnp.abs(hi), axis=1, keepdims=True)
    if shared:                                       # one block spans both
        a_lo = a_hi = jnp.maximum(a_lo, a_hi)
    s_lo = jnp.maximum(a_lo / 7.0, 1e-12)
    s_hi = jnp.maximum(a_hi / 7.0, 1e-12)
    q_lo = jnp.clip(jnp.round(lo / s_lo), -7, 7).astype(jnp.int32) & 0xF
    q_hi = jnp.clip(jnp.round(hi / s_hi), -7, 7).astype(jnp.int32) & 0xF
    # half-split nibble pack: low nibble = elements [0, W/2), high nibble =
    # [W/2, W) — the two halves are separate tiles, so no lane shuffle
    p_ref[...] = (q_lo | (q_hi << 4)).astype(jnp.uint8)
    scale_ref[0] = s_lo
    scale_ref[1] = s_hi


def gather_quantize4_pallas(x: jnp.ndarray, idx: jnp.ndarray, *,
                            block: int = Q4_BLOCK, interpret: bool = True):
    """Fused gather + blockwise-int4 quantize over CHANGED chunk rows.

    Same scalar-prefetch gather shape as :func:`gather_quantize_pallas`, but
    each row quantizes to signed int4 (clip ±7) and packs two nibbles per
    byte with the half-split layout (element j in the low nibble of byte j,
    element j + W/2 in its high nibble). A row arrives as a squeezed
    [2, m, L] tile: its two halves, each m sub-blocks of L lanes (or, when
    one block is the whole row, one L = W/2 slice per half sharing a
    scale). Returns (packed uint8 [C, W // 2], scales f32
    [C, W // block])."""
    G, W = x.shape
    C = int(idx.shape[0])
    n_sub = W // block
    shared = n_sub == 1
    assert W % block == 0 and (shared or n_sub % 2 == 0), (W, block)
    m, L = (1, W // 2) if shared else (n_sub // 2, block)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C,),
        in_specs=[pl.BlockSpec((None, 2, m, L),
                               lambda i, idx_ref: (idx_ref[i], 0, 0, 0))],
        out_specs=[pl.BlockSpec((None, m, L),
                                lambda i, idx_ref: (i, 0, 0)),
                   pl.BlockSpec((None, 2, m, 1),
                                lambda i, idx_ref: (i, 0, 0, 0))],
    )
    packed, scale = pl.pallas_call(
        functools.partial(_gather_quant4_kernel, shared=shared),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((C, m, L), jnp.uint8),
                   jax.ShapeDtypeStruct((C, 2, m, 1), jnp.float32)],
        interpret=interpret,
        name="gather_quantize4",
    )(idx, x.reshape(G, 2, m, L))
    return packed.reshape(C, W // 2), scale.reshape(C, 2 * m)[:, :n_sub]


def _dequant_kernel(q_ref, scale_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[...][:, None]


def dequantize_pallas(q: jnp.ndarray, scale: jnp.ndarray, *,
                      interpret: bool = True, tile_g: int = TILE_G):
    G, B = q.shape
    assert G % tile_g == 0
    return pl.pallas_call(
        _dequant_kernel,
        grid=(G // tile_g,),
        in_specs=[pl.BlockSpec((tile_g, B), lambda i: (i, 0)),
                  pl.BlockSpec((tile_g,), lambda i: (i,))],
        out_specs=pl.BlockSpec((tile_g, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, B), jnp.float32),
        interpret=interpret,
    )(q, scale)
