"""Pallas TPU kernel: chunk fingerprint + changed-mask (lean checkpointing).

The async writer wants to know WHICH chunks of a leaf changed since the last
materialized checkpoint without DMA-ing the whole leaf to the host. This
kernel computes a position-mixed 64-bit digest per chunk ON DEVICE; only
chunks whose digest changed are transferred. Integer multiply-add streams at
HBM bandwidth on the VPU, so fingerprinting costs one read of the leaf.

Tiling: the [G, B] uint32 view is processed in (TILE_G, B) VMEM blocks; B is
the checkpoint chunk size in words (4 KiB chunks = 1024 words by default),
TILE_G chosen so the block fits comfortably in VMEM (TILE_G * B * 4 bytes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import FP_PRIME1, FP_PRIME2, FP_PRIME3

TILE_G = 8


def _lane_reduce(v, op):
    """Reduce a [T, B] tile over its lanes with an associative, commutative
    ``op``; every lane of the [T, L] result holds the total. Folds the
    128-lane slices into one vreg, then butterflies with lane rotations —
    the TPU lowering has no xor reduction, and both ops give the same bits
    in any order (uint32 addition wraps). B is a multiple of 128 or a power
    of two."""
    B = v.shape[-1]
    lanes = min(B, 128)
    acc = v[:, :lanes]
    for k in range(lanes, B, lanes):
        acc = op(acc, v[:, k:k + lanes])
    shift = lanes // 2
    while shift:
        acc = op(acc, pltpu.roll(acc, shift, 1))
        shift //= 2
    return acc


def _digest(x):
    """[T, B] uint32 tile -> [T, 2] uint32 (xor fold, wrapping sum); the
    same math as ``fingerprint_ref``."""
    B = x.shape[-1]
    pos = (jax.lax.broadcasted_iota(jnp.uint32, (1, B), 1) * FP_PRIME1)
    v = (x ^ pos) * FP_PRIME2
    d0 = _lane_reduce(v, jnp.bitwise_xor)
    d1 = _lane_reduce(v * FP_PRIME3, jnp.add)
    lane = jax.lax.broadcasted_iota(jnp.int32, d0.shape, 1)
    return jnp.where(lane == 0, d0, d1)[:, :2]


def _fingerprint_kernel(x_ref, digest_ref):
    digest_ref[...] = _digest(x_ref[...])


def fingerprint_pallas(x_u32: jnp.ndarray, *, interpret: bool = True,
                       tile_g: int = TILE_G) -> jnp.ndarray:
    """[G, B] uint32 -> [G, 2] uint32 digests."""
    G, B = x_u32.shape
    assert G % tile_g == 0, (G, tile_g)
    return pl.pallas_call(
        _fingerprint_kernel,
        grid=(G // tile_g,),
        in_specs=[pl.BlockSpec((tile_g, B), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_g, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((G, 2), jnp.uint32),
        interpret=interpret,
    )(x_u32)


def _fp_changed_kernel(x_ref, prev_ref, digest_ref, mask_ref):
    d = _digest(x_ref[...])                          # [TILE_G, 2]
    digest_ref[...] = d
    ne = (d != prev_ref[...]).astype(jnp.int32)
    mask_ref[...] = jnp.max(ne, axis=1, keepdims=True)


def fingerprint_changed_pallas(x_u32: jnp.ndarray, prev: jnp.ndarray, *,
                               interpret: bool = True,
                               tile_g: int = TILE_G):
    """Fused digest + compare: [G, B] uint32 x [G, 2] prev digests ->
    ([G, 2] digests, [G] int32 changed mask) in ONE pass over the leaf.

    The separate ``fingerprint_pallas`` + ``changed_mask_pallas`` pair costs
    a second kernel launch and re-reads the [G, 2] digests from HBM; fusing
    the compare into the fingerprint tile keeps both outputs in registers
    while the leaf streams through VMEM once."""
    G, B = x_u32.shape
    assert G % tile_g == 0, (G, tile_g)
    digest, mask = pl.pallas_call(
        _fp_changed_kernel,
        grid=(G // tile_g,),
        in_specs=[pl.BlockSpec((tile_g, B), lambda i: (i, 0)),
                  pl.BlockSpec((tile_g, 2), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((tile_g, 2), lambda i: (i, 0)),
                   pl.BlockSpec((tile_g, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((G, 2), jnp.uint32),
                   jax.ShapeDtypeStruct((G, 1), jnp.int32)],
        interpret=interpret,
    )(x_u32, prev)
    return digest, mask.reshape(G)


def _changed_kernel(digest_ref, prev_ref, mask_ref):
    d = digest_ref[...]
    p = prev_ref[...]
    mask_ref[...] = jnp.any(d != p, axis=1).astype(jnp.int32)


def changed_mask_pallas(digest: jnp.ndarray, prev: jnp.ndarray, *,
                        interpret: bool = True,
                        tile_g: int = TILE_G) -> jnp.ndarray:
    G = digest.shape[0]
    assert G % tile_g == 0
    return pl.pallas_call(
        _changed_kernel,
        grid=(G // tile_g,),
        in_specs=[pl.BlockSpec((tile_g, 2), lambda i: (i, 0)),
                  pl.BlockSpec((tile_g, 2), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_g,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((G,), jnp.int32),
        interpret=interpret,
    )(digest, prev)
