"""MLA's causal flash attention at Moonlight's head widths (qk 192, v 128) on
the CPU: ``kernels/ops.py`` ``causal_attention`` (the splash forward, dq and
dkv kernels, in interpret mode here) against the chunked softmax it replaces
on TPU and against a dense float32 causal softmax, forward and the gradients
of q, k and v; and the dispatch in ``models/mla.py``, which keeps the chunked
path off TPU, under a sequence-sharded config and under a mesh of more than
one device."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import repro.configs as C
from repro.configs.base import MLAConfig
from repro.kernels import ops
from repro.models import build_model, mla
from repro.models.attention import _chunked_sdpa
from repro.parallel import use_mesh

B, H, DQK, DV = 2, 2, 192, 128
# (S, block): two sequence lengths, each tiled at two block sizes
CASES = [(256, 128), (256, 256), (512, 128), (512, 256)]
# relative error of the kernel's output or gradient: float32 is exact but
# for summation order; bfloat16 rounds q (with the scale folded in), the
# output and the gradients once, and the backward's p and dS operands
TOL = {"float32": 1e-5, "bfloat16": 1e-2}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@functools.lru_cache(maxsize=None)
def _inputs(S, dtype):
    kq, kk, kv, kc = jax.random.split(jax.random.key(S), 4)
    dt = jnp.dtype(dtype)
    q = jax.random.normal(kq, (B, H, S, DQK), jnp.float32).astype(dt)
    k = jax.random.normal(kk, (B, H, S, DQK), jnp.float32).astype(dt)
    v = jax.random.normal(kv, (B, H, S, DV), jnp.float32).astype(dt)
    ct = jax.random.normal(kc, (B, H, S, DV), jnp.float32)
    return q, k, v, ct


def _dense(q, k, v):
    """Causal softmax attention in float32, the whole score matrix."""
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    S = q.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(DQK)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def _chunked(q, k, v):
    """The chunked path MLA keeps off TPU: heads as the kv axis, v padded to
    q/k's head dim and sliced after."""
    S = q.shape[2]
    pos = jnp.arange(S, dtype=jnp.int32)
    qc = q.transpose(0, 2, 1, 3)[:, :, :, None]
    kc = k.transpose(0, 2, 1, 3)
    vc = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0),) * 3 + ((0, DQK - DV),))
    o = _chunked_sdpa(qc, kc, vc, pos, pos, True, None, 1.0 / np.sqrt(DQK),
                      128)
    return o[:, :, :, 0, :DV].transpose(0, 2, 1, 3)


def _out_and_grads(fn, S, dtype):
    q, k, v, ct = _inputs(S, dtype)
    o, vjp = jax.vjp(fn, q, k, v)
    return o, vjp(ct.astype(o.dtype))


@functools.lru_cache(maxsize=None)
def _kernel(S, block, dtype):
    return _out_and_grads(jax.jit(functools.partial(ops.causal_attention,
                                                    block=block)), S, dtype)


@functools.lru_cache(maxsize=None)
def _reference(name, S, dtype):
    return _out_and_grads(jax.jit({"dense": _dense,
                                   "chunked": _chunked}[name]), S, dtype)


@pytest.mark.parametrize("ref", ["dense", "chunked"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("S,block", CASES)
def test_causal_attention_forward(S, block, dtype, ref):
    o, _ = _kernel(S, block, dtype)
    want, _ = _reference(ref, S, dtype)
    assert o.shape == (B, H, S, DV) and o.dtype == jnp.dtype(dtype)
    assert _rel(o, want) < TOL[dtype]


@pytest.mark.parametrize("ref", ["dense", "chunked"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("S,block", CASES)
def test_causal_attention_gradients(S, block, dtype, ref):
    _, grads = _kernel(S, block, dtype)
    _, want = _reference(ref, S, dtype)
    for name, g, w in zip("qkv", grads, want):
        assert g.shape == w.shape and g.dtype == jnp.dtype(dtype), name
        assert _rel(g, w) < TOL[dtype], name


def test_causal_attention_is_causal():
    """Changing the last key and value moves only the last query's output."""
    q, k, v, _ = _inputs(256, "float32")
    o = ops.causal_attention(q, k, v)
    o2 = ops.causal_attention(q, k.at[:, :, -1].add(1.0),
                              v.at[:, :, -1].add(1.0))
    np.testing.assert_array_equal(np.asarray(o[:, :, :-1]),
                                  np.asarray(o2[:, :, :-1]))
    assert not np.allclose(np.asarray(o[:, :, -1]), np.asarray(o2[:, :, -1]))


def test_attention_block_divides_the_sequence():
    assert ops.attention_block(8192) in ops.ATTENTION_BLOCKS
    for S in (128, 256, 384, 512, 1024, 8192):
        assert S % ops.attention_block(S) == 0
    assert ops.attention_block(1000) is None


# ----------------------------------------------------------- dispatch ---

def _mla_case(S):
    """Moonlight's MLA head widths over a small model, f32, and one layer's
    attention parameters."""
    cfg = C.get_smoke("moonlight-16b-a3b").replace(
        dtype="float32", param_dtype="float32", num_heads=2, num_kv_heads=2,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=128,
                      qk_rope_head_dim=64, v_head_dim=DV))
    params = build_model(cfg).init(jax.random.PRNGKey(3))
    p = jax.tree_util.tree_map(lambda a: a[0], params["layers"]["attn"])
    x = jax.random.normal(jax.random.key(4), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return cfg, p, x, pos


def _chunked_mla(cfg, p, x, positions):
    """``mla_attention``'s chunked path as it stands beside the kernel."""
    m = cfg.mla
    H = cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    q_nope, q_rope, c_kv, k_r = mla._latents(cfg, p, x, positions)
    k_nope = jnp.einsum("bsr,rnh->bsnh", c_kv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rnh->bsnh", c_kv, p["w_uv"].astype(x.dtype))
    B_, S = x.shape[:2]
    q_eff = jnp.concatenate([q_nope, q_rope], axis=-1).reshape(B_, S, H, 1,
                                                                qk_hd)
    k_eff = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :],
                                  (B_, S, H, m.qk_rope_head_dim))], axis=-1)
    o = _chunked_sdpa(q_eff, k_eff,
                      jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                                  (0, qk_hd - m.v_head_dim))),
                      positions[0], positions[0], True, None,
                      1.0 / np.sqrt(qk_hd), cfg.attention_chunk,
                      probs_dtype=cfg.attention_probs_dtype,
                      remat_chunk=cfg.attention_remat_chunk,
                      seq_sharded=cfg.seq_shard)
    o = o.reshape(B_, S, H, qk_hd)[..., : m.v_head_dim]
    return jnp.einsum("bsnh,nhd->bsd", o, p["wo"].astype(x.dtype))


def test_mla_attention_on_cpu_takes_the_chunked_path(monkeypatch):
    cfg, p, x, pos = _mla_case(256)
    cfg = cfg.replace(attention_chunk=64)

    def refused(*a, **k):
        raise AssertionError("the kernel ran off TPU")
    monkeypatch.setattr(ops, "causal_attention", refused)
    got = jax.jit(lambda p, x: mla.mla_attention(cfg, p, x, pos))(p, x)
    want = jax.jit(lambda p, x: _chunked_mla(cfg, p, x, pos))(p, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mla_attention_kernel_path_matches_the_chunked_path(monkeypatch):
    """The kernel path's layouts (heads-major, v at its own width), taken on
    the CPU by forcing the dispatch, against the chunked path: the output
    and the gradients of the layer's parameters."""
    cfg, p, x, pos = _mla_case(256)

    def loss(fn, p):
        return jnp.sum(jnp.sin(fn(cfg, p, x, pos)))
    want, want_g = jax.jit(jax.value_and_grad(
        functools.partial(loss, _chunked_mla)))(p)
    monkeypatch.setattr(mla, "_flash", lambda cfg, seq: True)
    got, got_g = jax.jit(jax.value_and_grad(
        functools.partial(loss, mla.mla_attention)))(p)
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want)) + 1e-4
    for name in sorted(want_g):
        assert _rel(got_g[name], want_g[name]) < 1e-4, name


def test_flash_dispatch_follows_platform_shape_and_mesh(monkeypatch):
    cfg = C.get("moonlight-16b-a3b")
    assert not mla._flash(cfg, 8192)              # the CPU: chunked
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    assert mla._flash(cfg, 8192)
    assert not mla._flash(cfg, 1000)              # no block divides it
    assert not mla._flash(dataclasses.replace(cfg, seq_shard=True), 8192)
    with use_mesh(Mesh(np.array(jax.devices()[:1]), ("data",))):
        assert mla._flash(cfg, 8192)              # one device: the kernel

    class TwoDevices:
        size = 2
    monkeypatch.setattr(mla, "current_mesh", lambda: TwoDevices())
    assert not mla._flash(cfg, 8192)
