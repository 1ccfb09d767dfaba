"""The record-path Pallas kernels compile for a TPU v5e at full
florbench-100m widths (no chip needed: the TPU compiler runs against a
described topology). Guards what interpret mode cannot see — Mosaic's
block-shape and lowering rules — at no chip time."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.checkpoint.pipeline import PIPELINE_CHUNK_WORDS
from repro.kernels import ops
from repro.kernels.chunk_delta import (fingerprint_changed_pallas,
                                       fingerprint_pallas)
from repro.kernels.quantize import (Q4_BLOCK, Q8_BLOCK,
                                    gather_quantize4_pallas,
                                    gather_quantize_pallas)

# florbench-100m leaves: the tied 32768x768 f32 embedding, and a d_model x
# d_ff MLP weight in bf16
LEAVES = {"embed_f32": ((32768, 768), jnp.float32),
          "mlp_bf16": ((768, 3072), jnp.bfloat16)}
# the ops default chunk and the record pipeline's chunk
WIDTHS = (ops.CHUNK_WORDS, PIPELINE_CHUNK_WORDS)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _rows(shape, dtype, chunk_words):
    return jax.eval_shape(lambda x: ops._as_u32_blocks(x, chunk_words),
                          jax.ShapeDtypeStruct(shape, dtype)).shape[0]


def _compile_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("cw", WIDTHS)
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_fingerprint_compiles(one_chip, leaf, cw):
    shape, dtype = LEAVES[leaf]
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    _compile_text(lambda x: fingerprint_pallas(ops._as_u32_blocks(x, cw),
                                               interpret=False), x)


@pytest.mark.parametrize("cw", WIDTHS)
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_fingerprint_changed_compiles(one_chip, leaf, cw):
    shape, dtype = LEAVES[leaf]
    g = _rows(shape, dtype, cw)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    prev = jax.ShapeDtypeStruct((g, 2), jnp.uint32, sharding=one_chip)
    _compile_text(lambda x, p: fingerprint_changed_pallas(
        ops._as_u32_blocks(x, cw), p, interpret=False), x, prev)


@pytest.mark.parametrize("all_rows", [False, True], ids=["C64", "CG"])
@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("cw", WIDTHS)
@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_gather_quantize_compiles(one_chip, leaf, cw, bits, all_rows):
    shape, dtype = LEAVES[leaf]
    c = _rows(shape, dtype, cw) if all_rows else 64
    kernel, block = ((gather_quantize_pallas, Q8_BLOCK) if bits == 8
                     else (gather_quantize4_pallas, Q4_BLOCK))
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((c,), jnp.int32, sharding=one_chip)
    _compile_text(lambda x, i: kernel(ops._padded_float_blocks(x, cw), i,
                                      block=min(block, cw), interpret=False),
                  x, idx)
