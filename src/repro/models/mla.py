"""DeepSeek-V3 Multi-head Latent Attention.

Train path expands the latent to per-head K/V: on one TPU it runs the causal
splash kernels (``kernels/ops.py`` ``causal_attention``), elsewhere the
generic chunked softmax. Decode uses the ABSORBED form: the cache holds only
the compressed latent c_kv [B,S,r_kv] + shared rope key k_r [B,S,r_rope] —
the paper-relevant KV-compression trick — and W_uk/W_uv are absorbed into the
query/output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models.attention import _chunked_sdpa, _mask, NEG_INF
from repro.models.layers import apply_rope, dense_spec, rms_norm
from repro.models.params import ParamSpec
from repro.parallel import constrain, current_mesh


def mla_spec(cfg):
    m = cfg.mla
    d, H = cfg.d_model, cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    hax = "heads" if cfg.dense_layout == "tp" else None
    if m.q_lora_rank is None:                     # direct query projection
        q = {"w_q": dense_spec((d, H, qk_hd), ("embed", hax, None), fan_in=d)}
    else:
        q = {"w_dq": dense_spec((d, m.q_lora_rank), ("embed", None)),
             "q_ln": ParamSpec((m.q_lora_rank,), (None,), init="ones"),
             "w_uq": dense_spec((m.q_lora_rank, H, qk_hd), (None, hax, None),
                                fan_in=m.q_lora_rank)}
    return {
        **q,
        "w_dkv": dense_spec((d, m.kv_lora_rank), ("embed", None)),
        "kv_ln": ParamSpec((m.kv_lora_rank,), (None,), init="ones"),
        "w_kr": dense_spec((d, m.qk_rope_head_dim), ("embed", None)),
        "w_uk": dense_spec((m.kv_lora_rank, H, m.qk_nope_head_dim),
                           (None, hax, None), fan_in=m.kv_lora_rank),
        "w_uv": dense_spec((m.kv_lora_rank, H, m.v_head_dim),
                           (None, hax, None), fan_in=m.kv_lora_rank),
        "wo": dense_spec((H, m.v_head_dim, d), (hax, None, "embed"),
                         fan_in=H * m.v_head_dim),
    }


def _latents(cfg, p, x, positions, rope=None):
    """Shared q / kv latent computation. Returns (q_nope, q_rope, c_kv, k_r)."""
    m = cfg.mla
    if "w_q" in p:
        q = jnp.einsum("bsd,dnh->bsnh", x, p["w_q"].astype(x.dtype))
    else:
        cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(x.dtype)),
                      p["q_ln"], cfg.norm_eps)
        q = jnp.einsum("bsr,rnh->bsnh", cq, p["w_uq"].astype(x.dtype))
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions[:, :, None],
                        cfg.rope_theta, tables=rope)
    c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype)),
                    p["kv_ln"], cfg.norm_eps)
    k_r = apply_rope(jnp.einsum("bsd,dr->bsr", x, p["w_kr"].astype(x.dtype)),
                     positions, cfg.rope_theta, tables=rope)
    return q_nope, q_rope, c_kv, k_r


def _flash(cfg, seq: int) -> bool:
    """Whether the training attention runs ``ops.causal_attention``: on TPU,
    at a sequence its blocks divide, unsharded, under no mesh of more than
    one device."""
    mesh = current_mesh()
    return (not ops._interpret() and ops.attention_block(seq) is not None
            and not cfg.seq_shard and (mesh is None or mesh.size == 1))


def mla_attention(cfg, p, x, positions, rope=None):
    """Training/prefill forward (expanded form + causal flash kernel or
    chunked softmax)."""
    m = cfg.mla
    H = cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    scale = 1.0 / np.sqrt(qk_hd)
    q_nope, q_rope, c_kv, k_r = _latents(cfg, p, x, positions, rope=rope)
    k_nope = jnp.einsum("bsr,rnh->bsnh", c_kv, p["w_uk"].astype(x.dtype))
    v = jnp.einsum("bsr,rnh->bsnh", c_kv, p["w_uv"].astype(x.dtype))
    B, S = x.shape[:2]
    if _flash(cfg, S):
        # heads-major for the kernel; v keeps its own head dim
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, :, None, :],
                                      (B, S, H, m.qk_rope_head_dim))], axis=-1)
        o = ops.causal_attention(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)))
        return jnp.einsum("bnsh,nhd->bsd", o, p["wo"].astype(x.dtype))
    # assemble effective q/k with heads as the "KV" axis (G=1) so we can reuse
    # the generic chunked online-softmax
    q_eff = jnp.concatenate([q_nope, q_rope], axis=-1).reshape(B, S, H, 1, qk_hd)
    k_eff = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :], (B, S, H, m.qk_rope_head_dim))],
        axis=-1)
    # pad v up to qk_hd so k/v share a head_dim (cheap: zero-pad, slice after)
    q_eff = constrain(q_eff, ("batch", None, "act_heads", None, None))
    k_eff = constrain(k_eff, ("batch", None, "act_heads", None))
    o = _chunked_sdpa(q_eff, k_eff,
                      jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_hd - m.v_head_dim))),
                      positions[0], positions[0], True, None, scale,
                      cfg.attention_chunk,
                      probs_dtype=cfg.attention_probs_dtype,
                      remat_chunk=cfg.attention_remat_chunk,
                      seq_sharded=cfg.seq_shard)
    o = o.reshape(B, S, H, qk_hd)[..., : m.v_head_dim]
    return jnp.einsum("bsnh,nhd->bsd", o, p["wo"].astype(x.dtype))


# ------------------------------------------------------------- decode -----

def mla_cache_spec(cfg, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "c_kv": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dtype),
        "k_r": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_head_dim), dtype),
        "slot_pos": jax.ShapeDtypeStruct((max_len,), jnp.int32),
    }


def mla_cache_axes():
    return {"c_kv": ("batch", "cache_seq", None),
            "k_r": ("batch", "cache_seq", None),
            "slot_pos": (None,)}


def mla_init_cache(cfg, batch, max_len, dtype):
    spec = mla_cache_spec(cfg, batch, max_len, dtype)
    c = {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}
    c["slot_pos"] = jnp.full((max_len,), -1, jnp.int32)
    return c


def mla_decode(cfg, p, x, cache, pos):
    """Absorbed-form one-token decode against the compressed latent cache."""
    m = cfg.mla
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    scale = 1.0 / np.sqrt(qk_hd)
    B = x.shape[0]
    posv = jnp.full((B, 1), pos, jnp.int32)
    q_nope, q_rope, c_kv_new, k_r_new = _latents(cfg, p, x, posv)

    ckv = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), (0, pos, 0))
    kr = jax.lax.dynamic_update_slice(
        cache["k_r"], k_r_new.astype(cache["k_r"].dtype), (0, pos, 0))
    slot_pos = jax.lax.dynamic_update_slice(
        cache["slot_pos"], jnp.full((1,), pos, jnp.int32), (pos,))
    ckv = constrain(ckv, ("batch", "cache_seq", None))

    # absorb W_uk into q: q_abs [B,1,H,r_kv]
    q_abs = jnp.einsum("bqnh,rnh->bqnr", q_nope, p["w_uk"].astype(x.dtype))
    s = (jnp.einsum("bqnr,bkr->bnqk", q_abs.astype(jnp.float32),
                    ckv.astype(jnp.float32))
         + jnp.einsum("bqnh,bkh->bnqk", q_rope.astype(jnp.float32),
                      kr.astype(jnp.float32))) * scale
    keep = _mask(jnp.full((1,), pos, jnp.int32), slot_pos, True, None)
    s = jnp.where(keep[None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bnqk,bkr->bqnr", w, ckv.astype(jnp.float32))
    o = jnp.einsum("bqnr,rnh->bqnh", ctx.astype(x.dtype), p["w_uv"].astype(x.dtype))
    out = jnp.einsum("bqnh,nhd->bqd", o, p["wo"].astype(x.dtype))
    return out, {"c_kv": ckv, "k_r": kr, "slot_pos": slot_pos}


def mla_prefill_cache(cfg, p, x, positions, max_len, dtype, rope=None):
    m = cfg.mla
    c_kv = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(x.dtype)),
                    p["kv_ln"], cfg.norm_eps)
    k_r = apply_rope(jnp.einsum("bsd,dr->bsr", x, p["w_kr"].astype(x.dtype)),
                     positions, cfg.rope_theta, tables=rope)
    B, S = x.shape[:2]
    pad = max_len - S
    return {
        "c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))).astype(dtype),
        "k_r": jnp.pad(k_r, ((0, 0), (0, pad), (0, 0))).astype(dtype),
        "slot_pos": jnp.concatenate(
            [jnp.arange(S, dtype=jnp.int32), jnp.full((pad,), -1, jnp.int32)]),
    }
