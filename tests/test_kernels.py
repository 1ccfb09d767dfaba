"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.chunk_delta import (changed_mask_pallas,
                                       fingerprint_changed_pallas,
                                       fingerprint_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import (dequantize_pallas,
                                    gather_quantize4_pallas,
                                    gather_quantize_pallas, quantize_pallas)
from repro.kernels.ops import (
    dequantize_blocks, fingerprint_leaf, quantize_blocks)

from proptest import given, st


@pytest.mark.parametrize("g,b", [(8, 128), (16, 1024), (32, 256), (64, 64)])
def test_fingerprint_matches_ref(g, b):
    x = jax.random.bits(jax.random.PRNGKey(g * b), (g, b), jnp.uint32)
    np.testing.assert_array_equal(np.asarray(fingerprint_pallas(x)),
                                  np.asarray(ref.fingerprint_ref(x)))


@pytest.mark.parametrize("g,b", [(8, 128), (16, 1024), (16, 384), (64, 64)])
def test_fingerprint_changed_matches_ref(g, b):
    """The fused kernel's digests and (G, 1)-laid mask equal the oracle."""
    x = jax.random.bits(jax.random.PRNGKey(g + b), (g, b), jnp.uint32)
    prev = ref.fingerprint_ref(x).at[1, 0].add(1).at[g - 1, 1].add(1)
    d, mask = fingerprint_changed_pallas(x, prev)
    d_r, mask_r = ref.fingerprint_changed_ref(x, prev)
    np.testing.assert_array_equal(np.asarray(d), np.asarray(d_r))
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_r))
    assert mask.shape == (g,) and mask.dtype == jnp.int32
    assert np.flatnonzero(np.asarray(mask)).tolist() == [1, g - 1]


@pytest.mark.parametrize("w,block", [(1024, 256), (512, 256), (256, 256)])
def test_gather_quantize_matches_ref(w, block):
    """q8 and q4 gathers over squeezed row tiles: scales to float rounding,
    codes to one level (the kernel and the oracle may divide differently);
    (256, 256) is the one-block-per-row case q4 splits across halves."""
    x = jax.random.normal(jax.random.PRNGKey(w), (16, w)) * 3.0
    idx = jnp.asarray([1, 4, 7, 15], jnp.int32)
    q, s = gather_quantize_pallas(x, idx, block=block)
    q_r, s_r = ref.gather_quantize_ref(x, idx, block)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r), rtol=1e-6)
    assert np.max(np.abs(np.asarray(q, np.int32)
                         - np.asarray(q_r, np.int32))) <= 1
    p, s = gather_quantize4_pallas(x, idx, block=block)
    p_r, s_r = ref.gather_quantize4_ref(x, idx, block)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r), rtol=1e-6)
    for a, b in ((np.asarray(p) & 0xF, np.asarray(p_r) & 0xF),
                 (np.asarray(p) >> 4, np.asarray(p_r) >> 4)):
        d = (a.astype(np.int8) - ((a > 7) << 4)) \
            - (b.astype(np.int8) - ((b > 7) << 4))
        assert np.max(np.abs(d)) <= 1
    assert p.shape == (4, w // 2) and s.shape == (4, w // block)


def test_fingerprint_detects_single_bit_flip():
    x = jax.random.bits(jax.random.PRNGKey(0), (16, 512), jnp.uint32)
    base = fingerprint_pallas(x)
    for (i, j) in [(0, 0), (7, 511), (15, 100)]:
        x2 = x.at[i, j].set(x[i, j] ^ np.uint32(1))
        mask = changed_mask_pallas(fingerprint_pallas(x2), base)
        assert int(mask[i]) == 1 and int(mask.sum()) == 1


def test_fingerprint_position_sensitivity():
    """Swapping two words within a chunk must change its digest."""
    x = jax.random.bits(jax.random.PRNGKey(3), (8, 64), jnp.uint32)
    sw = x.at[2, 0].set(x[2, 1]).at[2, 1].set(x[2, 0])
    assert int(changed_mask_pallas(fingerprint_pallas(sw),
                                   fingerprint_pallas(x))[2]) == 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
@pytest.mark.parametrize("shape", [(100,), (33, 7), (5, 6, 7)])
def test_fingerprint_leaf_any_shape_dtype(dtype, shape):
    x = jax.random.normal(jax.random.PRNGKey(1), shape).astype(dtype)
    d1 = fingerprint_leaf(x, 64)
    d2 = fingerprint_leaf(x, 64)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    x2 = x.reshape(-1).at[0].set(jnp.asarray(1.5, x.dtype)).reshape(shape)
    if float(x.reshape(-1)[0]) != 1.5:
        assert not np.array_equal(np.asarray(fingerprint_leaf(x2, 64)),
                                  np.asarray(d1))


@pytest.mark.parametrize("g,b", [(8, 256), (16, 128), (40, 512)])
@pytest.mark.parametrize("scale", [1e-4, 1.0, 1e4])
def test_quantize_matches_ref(g, b, scale):
    x = jax.random.normal(jax.random.PRNGKey(g + b), (g, b)) * scale
    qp, sp = quantize_pallas(x)
    qr, sr = ref.quantize_ref(x)
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), rtol=1e-6)
    # error bound: |x - deq| <= scale/2 per block
    deq = dequantize_pallas(qp, sp)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    bound = np.asarray(sp)[:, None] * 0.5 + 1e-9
    assert (err <= bound).all()


@given(n=st.integers(1, 5000))
def test_quantize_blocks_roundtrip_any_size(n):
    x = jax.random.normal(jax.random.PRNGKey(n), (n,))
    q, s = quantize_blocks(x, block=256)
    back = dequantize_blocks(q, s, (n,), jnp.float32)
    err = np.max(np.abs(np.asarray(back) - np.asarray(x)))
    assert err <= float(s.max()) * 0.5 + 1e-9


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("cfg", [
    # B, H, KV, Sq, Sk, d, bq, bk, causal
    (1, 2, 2, 128, 128, 64, 64, 64, True),
    (2, 4, 2, 128, 128, 64, 128, 128, True),
    (1, 8, 1, 64, 256, 32, 64, 64, True),     # MQA, decode-ish Sq<Sk
    (2, 2, 2, 128, 128, 128, 64, 32, False),  # bidirectional
])
def test_flash_attention_matches_ref(dtype, cfg):
    B, H, KV, Sq, Sk, d, bq, bk, causal = cfg
    ks = jax.random.split(jax.random.PRNGKey(sum(cfg)), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (B, KV, Sk, d)).astype(dtype)
    v = jax.random.normal(ks[2], (B, KV, Sk, d)).astype(dtype)
    o_p = flash_attention_pallas(q, k, v, causal=causal, block_q=bq, block_k=bk)
    o_r = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_p, np.float32),
                               np.asarray(o_r, np.float32), atol=tol, rtol=tol)


def test_flash_block_shape_invariance():
    """Output must not depend on the BlockSpec tiling."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    outs = [flash_attention_pallas(q, k, v, block_q=bq, block_k=bk)
            for bq, bk in [(64, 64), (128, 64), (256, 128), (128, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   atol=1e-5)


def test_gradient_compression_error_feedback():
    from repro.parallel.compression import (
        compress_grads_with_feedback, decompress_grads, init_error_state)
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (300,)),
         "b": jax.random.normal(jax.random.PRNGKey(1), (7,))}
    err = init_error_state(g)
    comp, err2 = compress_grads_with_feedback(g, err)
    deq = decompress_grads(comp, g)
    # error feedback: residual carried exactly
    resid = np.asarray(g["w"]) - np.asarray(deq["w"])
    np.testing.assert_allclose(np.asarray(err2["w"]), resid, atol=1e-6)
    # accumulated bias shrinks over repeated steps of the same gradient
    total = np.zeros(300, np.float32)
    err_state = init_error_state(g)
    for _ in range(8):
        comp, err_state = compress_grads_with_feedback(g, err_state)
        total += np.asarray(decompress_grads(comp, g)["w"])
    avg = total / 8
    assert np.abs(avg - np.asarray(g["w"])).max() < 0.02
