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


# the custom call each record-path kernel compiles to is named after its
# ``name=``, so a trace shows the kernel whatever program calls it; the
# fused pass must keep the prefix the fingerprint roofline reader matches
NAMED = {
    "fingerprint": lambda x, i, p: fingerprint_pallas(
        ops._as_u32_blocks(x, PIPELINE_CHUNK_WORDS), interpret=False),
    "fingerprint_changed": lambda x, i, p: fingerprint_changed_pallas(
        ops._as_u32_blocks(x, PIPELINE_CHUNK_WORDS), p, interpret=False),
    "gather_quantize8": lambda x, i, p: gather_quantize_pallas(
        ops._padded_float_blocks(x, PIPELINE_CHUNK_WORDS), i,
        block=Q8_BLOCK, interpret=False),
    "gather_quantize4": lambda x, i, p: gather_quantize4_pallas(
        ops._padded_float_blocks(x, PIPELINE_CHUNK_WORDS), i,
        block=Q4_BLOCK, interpret=False),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_record_kernels_carry_stable_names(one_chip, name):
    shape, dtype = LEAVES["mlp_bf16"]
    g = _rows(shape, dtype, PIPELINE_CHUNK_WORDS)
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    prev = jax.ShapeDtypeStruct((g, 2), jnp.uint32, sharding=one_chip)
    text = _compile_text(NAMED[name], x, idx, prev)
    calls = [ln.split("=")[0].strip().lstrip("%") for ln in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert calls and all(c.split(".")[0] == name for c in calls), calls


def test_raw_gather_program_is_named():
    x = jax.ShapeDtypeStruct((64, 1024), jnp.float32)
    idx = jax.ShapeDtypeStruct((8,), jnp.int32)
    text = ops.gather_changed_rows.lower(x, idx, chunk_words=1024).as_text()
    assert "@jit_gather_changed_rows" in text


# Moonlight-16B-A3B's expert products on one chip's share: 8 held experts,
# batch 2 x 8192 tokens x top-6 pairs in 256-row tiles
EXPERT_ROWS, EXPERT_TILE, EXPERTS_HELD = 100352, 256, 8
EXPERT_WEIGHTS = {"wi": (2048, 1408), "wo": (1408, 2048)}


@pytest.mark.parametrize("weight", sorted(EXPERT_WEIGHTS))
def test_expert_products_compile_and_carry_stable_names(one_chip, weight):
    K, N = EXPERT_WEIGHTS[weight]
    tiles = EXPERT_ROWS // EXPERT_TILE
    lhs = jax.ShapeDtypeStruct((EXPERT_ROWS, K), jnp.bfloat16,
                               sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((EXPERTS_HELD, K, N), jnp.bfloat16,
                               sharding=one_chip)
    tg = jax.ShapeDtypeStruct((tiles,), jnp.int32, sharding=one_chip)
    nt = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def fwd_bwd(lhs, rhs, tg, nt):
        def f(a, w):
            y = ops.expert_matmul_pallas(a, w, tg, nt, EXPERT_TILE, False)
            return jnp.sum(y.astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1))(lhs, rhs)
    text = _compile_text(fwd_bwd, lhs, rhs, tg, nt)
    calls = sorted({ln.split("=")[0].strip().lstrip("%").split(".")[0]
                    for ln in text.splitlines()
                    if "custom_call_target=\"tpu_custom_call\"" in ln})
    assert calls == ["expert_gmm", "expert_tgmm"], calls


# Moonlight-16B-A3B's training attention: batch 2 x 8192 tokens, 16 heads,
# q/k 192 wide and v 128
ATTN_SEQ, ATTN_HEADS = 8192, 16


def test_causal_attention_compiles_with_its_backward(one_chip, monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    qk = jax.ShapeDtypeStruct((2, ATTN_HEADS, ATTN_SEQ, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, ATTN_HEADS, ATTN_SEQ, 128), jnp.bfloat16,
                             sharding=one_chip)

    def fwd_bwd(q, k, v):
        def f(q, k, v):
            return jnp.sum(ops.causal_attention(q, k, v).astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    text = _compile_text(fwd_bwd, qk, qk, v)
    calls = sorted({ln.split("=")[0].strip().lstrip("%").split(".")[0]
                    for ln in text.splitlines()
                    if "custom_call_target=\"tpu_custom_call\"" in ln})
    assert calls == ["splash_mha_dkv_no_residuals",
                     "splash_mha_dq_no_residuals",
                     "splash_mha_fwd_residuals"], calls
    # every kernel's block table is [heads, S / block, S / block] at the
    # block the code chooses (one head here: the causal masks are equal)
    n = ATTN_SEQ // ops.attention_block(ATTN_SEQ)
    tables = {t for t in text.split() if t.startswith("s8[")}
    assert tables and all(t.startswith(f"s8[1,{n},{n}]") for t in tables), \
        tables
