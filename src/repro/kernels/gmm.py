"""Pallas TPU kernels: grouped matrix products over the rows of sorted
expert groups (dropless mixture-of-experts).

The rows of ``lhs`` are laid out in groups, one per expert, each group
starting on a ``tm``-row tile boundary and padded with zero rows up to the
next one, so every tile belongs to exactly one group. ``tile_group[i]``
names the group of row tile ``i``; only the first ``num_tiles`` tiles hold
rows, and the grid has that many steps, so the work follows the routed rows
(up to the padding of each group's last tile), not the buffer's static
size. Rows past ``num_tiles * tm`` are neither read nor written.

* ``expert_gmm``: ``out[r] = lhs[r] @ rhs[g(r)]`` (or ``rhs[g(r)].T``), the
  forward products and the input-gradient products;
* ``expert_tgmm``: ``out[g] = lhs[rows of g].T @ dout[rows of g]``, the
  weight gradients. A group with no tile gets zeros.

Each expert's weights are one block, so consecutive tiles of a group read
them once. Each ``pallas_call`` carries a ``name=``, which the profiler
trace shows as the kernel's op name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# an expert's whole weight matrix is one block (2048 x 1408 bf16 is 5.8 MB,
# double-buffered), beside the row tiles and the accumulator
VMEM_LIMIT = 64 * 1024 * 1024


def _gmm_kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, *, transpose_rhs):
    del tile_group_ref
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
        (((1,), (0,)), ((), ()))
    out_ref[...] = jax.lax.dot_general(
        lhs_ref[...], rhs_ref[...], dims,
        preferred_element_type=jnp.float32).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "transpose_rhs",
                                             "interpret"))
def expert_gmm(lhs, rhs, tile_group, num_tiles, *, tm: int,
               transpose_rhs: bool = False, interpret: bool = False):
    """lhs [M, K], rhs [G, K, N] ([G, N, K] with ``transpose_rhs``),
    tile_group int32 [M // tm], num_tiles int32 scalar -> [M, N]."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    grid = (jnp.asarray(num_tiles, jnp.int32),)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tm, K), lambda i, tg: (i, 0)),
                pl.BlockSpec((None,) + rhs.shape[1:],
                             lambda i, tg: (tg[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, N), lambda i, tg: (i, 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_gmm",
    )(tile_group, lhs, rhs)


def _tgmm_kernel(tile_group_ref, num_tiles_ref, lhs_ref, dout_ref, out_ref,
                 acc_ref):
    i = pl.program_id(0)
    last = num_tiles_ref[0] - 1
    g = tile_group_ref[i]
    first_of_group = (i == 0) | (tile_group_ref[jnp.maximum(i - 1, 0)] != g)
    last_of_group = (i == last) | (tile_group_ref[jnp.minimum(i + 1, last)]
                                   != g)

    @pl.when(first_of_group)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lhs_t = lhs_ref[...].astype(jnp.float32).swapaxes(0, 1)
    acc_ref[...] += jnp.dot(lhs_t.astype(lhs_ref.dtype), dout_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(last_of_group)
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_groups", "tm",
                                             "interpret"))
def expert_tgmm(lhs, dout, tile_group, num_tiles, group_rows, *,
                num_groups: int, tm: int, interpret: bool = False):
    """lhs [M, K], dout [M, N] -> [num_groups, K, N]; ``group_rows``
    [num_groups] (any count; zero marks a group with no tile)."""
    M, K = lhs.shape
    N = dout.shape[1]
    nt = jnp.asarray(num_tiles, jnp.int32)
    out = pl.pallas_call(
        _tgmm_kernel,
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt,),
            in_specs=[
                pl.BlockSpec((tm, K), lambda i, tg, n: (i, 0)),
                pl.BlockSpec((tm, N), lambda i, tg, n: (i, 0)),
            ],
            out_specs=pl.BlockSpec((None, K, N),
                                   lambda i, tg, n: (tg[i], 0, 0)),
            scratch_shapes=[pltpu.VMEM((K, N), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_tgmm",
    )(tile_group, nt.reshape(1), lhs, dout)
    # a group that no tile visits was never written
    return jnp.where((group_rows > 0)[:, None, None], out, 0)
