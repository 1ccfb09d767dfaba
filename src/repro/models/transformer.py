"""Decoder-only LM assembly for dense / moe / ssm / hybrid / vlm families.

Layer stacks are jax.lax.scan'd over stacked params (small HLO, GSPMD-sliced
FSDP gathers per iteration) with per-block jax.checkpoint (remat). The loss
is sequence-chunked so [B,S,vocab] logits never materialize for large-vocab
archs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn
from repro.models import mamba
from repro.models import mla
from repro.models import moe as moe_mod
from repro.models.layers import (
    embed_tokens,
    embedding_spec,
    lm_logits,
    mlp_apply,
    mlp_spec,
    norm_spec,
    padded_vocab_size,
    unembed_spec,
)
from repro.models.params import stack_spec
from repro.models.layers import rms_norm
from repro.parallel import constrain


def _remat(cfg, fn):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def padded_vocab(cfg) -> int:
    v = cfg.vocab_size
    return v if v < 512 else padded_vocab_size(v, 512)


# ------------------------------------------------------------- blocks -----

def dense_block_spec(cfg):
    spec = {
        "ln1": norm_spec(cfg.d_model),
        "attn": mla.mla_spec(cfg) if cfg.mla else attn.attn_spec(cfg),
        "ln2": norm_spec(cfg.d_model),
        "mlp": mlp_spec(cfg, cfg.d_ff),
    }
    return spec


def moe_block_spec(cfg):
    return {
        "ln1": norm_spec(cfg.d_model),
        "attn": mla.mla_spec(cfg) if cfg.mla else attn.attn_spec(cfg),
        "ln2": norm_spec(cfg.d_model),
        "moe": moe_mod.moe_spec(cfg),
    }


def _attention(cfg, p, x, positions, window, rope=None):
    if cfg.mla:
        return mla.mla_attention(cfg, p, x, positions, rope=rope)
    return attn.self_attention(cfg, p, x, positions, causal=True,
                               window=window, rope=rope)


def rope_tables_for(cfg, S: int):
    """Hoisted (cos, sin) rope tables — computed ONCE per forward and closed
    over by the layer scan (loop-invariant; saves ~8% HBM traffic)."""
    from repro.models.layers import rope_tables
    if cfg.family == "ssm":
        return None
    dim = cfg.mla.qk_rope_head_dim if cfg.mla else cfg.resolved_head_dim()
    return rope_tables(jnp.arange(S, dtype=jnp.int32), dim, cfg.rope_theta)


def res_axes(cfg):
    """Residual-stream logical axes. With cfg.seq_shard the sequence dim is
    sharded over 'model' (sequence parallelism) — the layout of choice when
    head counts don't divide the model axis and attention would replicate.
    With dense_layout='dp' the batch dim spreads over all mesh axes."""
    from repro.models.layers import batch_axis
    return (batch_axis(cfg), "seq_mp" if cfg.seq_shard else None, None)


def dense_block(cfg, p, x, positions, window=None, rope=None):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + _attention(cfg, p["attn"], h, positions, window, rope)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    x = x + mlp_apply(cfg, p["mlp"], h)
    return constrain(x, res_axes(cfg))


def moe_block(cfg, p, x, positions, window=None, rope=None):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + _attention(cfg, p["attn"], h, positions, window, rope)
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    y, metrics = moe_mod.moe_apply(cfg, p["moe"], h)
    x = x + y
    return constrain(x, res_axes(cfg)), metrics


# -------------------------------------------------------------- specs -----

def lm_param_spec(cfg):
    pv = padded_vocab(cfg)
    spec = {"embed": embedding_spec(cfg, pv), "ln_f": norm_spec(cfg.d_model)}
    if not cfg.tie_embeddings:
        spec["unembed"] = unembed_spec(cfg, pv)
    fam = cfg.family
    if fam in ("dense", "vlm"):
        spec["layers"] = stack_spec(dense_block_spec(cfg), cfg.num_layers)
    elif fam == "moe":
        nd = cfg.moe.first_dense_layers
        if nd:
            spec["dense_layers"] = stack_spec(dense_block_spec(cfg), nd)
        spec["layers"] = stack_spec(moe_block_spec(cfg), cfg.num_layers - nd)
    elif fam == "ssm":
        spec["layers"] = stack_spec(mamba.mamba1_spec(cfg), cfg.num_layers)
    elif fam == "hybrid":
        g = cfg.num_layers // cfg.attn_period
        per = cfg.attn_period - 1
        tail = cfg.num_layers - g * cfg.attn_period
        spec["groups"] = stack_spec(stack_spec(mamba.mamba2_spec(cfg), per), g)
        spec["shared_attn"] = dense_block_spec(cfg)
        if tail:
            spec["tail"] = stack_spec(mamba.mamba2_spec(cfg), tail)
    else:
        raise ValueError(fam)
    return spec


# ------------------------------------------------------------ forward -----

def _mamba_fwd(cfg):
    return mamba.mamba1_forward if cfg.ssm.version == 1 else mamba.mamba2_forward


def lm_forward(cfg, params, tokens=None, embeds=None):
    """Returns final hidden states [B, S_total, d]."""
    compute_dtype = jnp.dtype(cfg.dtype)
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(compute_dtype))
    if tokens is not None:
        parts.append(embed_tokens(cfg, params["embed"]["table"], tokens,
                                  compute_dtype))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    x = constrain(x, res_axes(cfg))
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    window = cfg.sliding_window
    fam = cfg.family

    rope = rope_tables_for(cfg, S)
    if fam in ("dense", "vlm"):
        body = _remat(cfg, lambda h, lyr: (dense_block(cfg, lyr, h, positions,
                                                       window, rope), None))
        x, _ = jax.lax.scan(body, x, params["layers"])
        metrics = {}
    elif fam == "moe":
        if "dense_layers" in params:
            dbody = _remat(cfg, lambda h, lyr: (dense_block(cfg, lyr, h,
                                                            positions, window,
                                                            rope), None))
            x, _ = jax.lax.scan(dbody, x, params["dense_layers"])
        def mbody(h, lyr):
            h2, m = moe_block(cfg, lyr, h, positions, window, rope)
            return h2, (m["moe_aux"], m["moe_rows"], m["moe_load_max"])
        x, (aux, rows, load) = jax.lax.scan(_remat(cfg, mbody), x,
                                            params["layers"])
        metrics = {"moe_aux": aux.mean(), "moe_rows": rows.sum(),
                   "moe_load_max": load.max()}
    elif fam == "ssm":
        fwd = _mamba_fwd(cfg)
        body = _remat(cfg, lambda h, lyr: (h + fwd(cfg, lyr, h), None))
        x, _ = jax.lax.scan(body, x, params["layers"])
        metrics = {}
    elif fam == "hybrid":
        fwd = mamba.mamba2_forward
        mamba_body = _remat(cfg, lambda h, lyr: (h + fwd(cfg, lyr, h), None))
        shared = params["shared_attn"]
        rope = rope_tables_for(cfg, S)
        def group_body(h, glyr):
            h, _ = jax.lax.scan(mamba_body, h, glyr)
            h = _remat(cfg, lambda hh: dense_block(cfg, shared, hh, positions,
                                                   window, rope))(h)
            return h, None
        x, _ = jax.lax.scan(group_body, x, params["groups"])
        if "tail" in params:
            x, _ = jax.lax.scan(mamba_body, x, params["tail"])
        metrics = {}
    else:
        raise ValueError(fam)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, metrics


# --------------------------------------------------------------- loss -----

def _loss_chunk_size(cfg, S):
    if cfg.loss_chunk:
        return min(cfg.loss_chunk, S)
    pv = padded_vocab(cfg)
    if S * pv > 64 * 1024 * 1024:
        return max(1, min(1024, S))
    return S


def ce_loss(cfg, params, hidden, labels, mask=None):
    """Chunked cross-entropy. hidden [B,T,d] aligned with labels [B,T]."""
    pv = padded_vocab(cfg)
    B, T, _ = hidden.shape
    if mask is None:
        mask = jnp.ones((B, T), jnp.float32)
    C = _loss_chunk_size(cfg, T)
    pad = (-T) % C
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nch = hidden.shape[1] // C

    def chunk_fn(h_c, y_c, m_c):
        logits = lm_logits(cfg, params, h_c, pv).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hot = jax.nn.one_hot(y_c, pv, dtype=jnp.bfloat16)
        gold = jnp.einsum("bsv,bsv->bs", logits, hot,
                          preferred_element_type=jnp.float32)
        nll = (lse - gold) * m_c
        return nll.sum(), m_c.sum(), (jnp.square(lse) * m_c).sum()

    if nch == 1:
        tot, cnt, zsq = chunk_fn(hidden, labels, mask)
    else:
        hs = hidden.reshape(B, nch, C, -1).swapaxes(0, 1)
        ys = labels.reshape(B, nch, C).swapaxes(0, 1)
        ms = mask.reshape(B, nch, C).swapaxes(0, 1)
        def body(carry, xs):
            t, c, z = carry
            dt_, dc, dz = jax.checkpoint(chunk_fn)(*xs)
            return (t + dt_, c + dc, z + dz), None
        (tot, cnt, zsq), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (hs, ys, ms))
    cnt = jnp.maximum(cnt, 1.0)
    return tot / cnt, {"ce": tot / cnt, "z_loss": zsq / cnt}


def lm_loss(cfg, params, batch):
    """Next-token loss for decoder-only families. batch: tokens [B,S] and,
    for vlm, embeds [B,F,d] prefix."""
    tokens = batch["tokens"]
    embeds = batch.get("embeds")
    hidden, metrics = lm_forward(cfg, params, tokens, embeds)
    if embeds is not None:
        F = embeds.shape[1]
        St = tokens.shape[1]
        h = hidden[:, F - 1: F + St - 1]
        loss, lm = ce_loss(cfg, params, h, tokens)
    else:
        loss, lm = ce_loss(cfg, params, hidden[:, :-1], tokens[:, 1:])
    metrics.update(lm)
    if cfg.moe is not None and cfg.moe.router_aux_loss and "moe_aux" in metrics:
        loss = loss + cfg.moe.router_aux_loss * metrics["moe_aux"]
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------- prefill / decode ----

def _attn_prefill(cfg, p, x, positions, max_len, dtype, window, rope=None):
    """Run one attention block AND emit its primed cache."""
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla:
        out = mla.mla_attention(cfg, p["attn"], h, positions, rope=rope)
        cache = mla.mla_prefill_cache(cfg, p["attn"], h, positions, max_len,
                                      dtype, rope=rope)
    else:
        out = attn.self_attention(cfg, p["attn"], h, positions, causal=True,
                                  window=window, rope=rope)
        cache = attn.prefill_cache(cfg, p["attn"], h, positions, max_len,
                                   dtype, rope=rope)
    x = x + out
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, _ = moe_mod.moe_apply(cfg, p["moe"], h)
    else:
        y = mlp_apply(cfg, p["mlp"], h)
    return x + y, cache


def _mamba_prefill(cfg, p, x):
    """Mamba block forward + final state cache (for decode continuation)."""
    fwd = mamba.mamba1_forward if cfg.ssm.version == 1 else mamba.mamba2_forward
    out, cache = fwd(cfg, p, x, return_cache=True)
    return x + out, cache


def lm_prefill(cfg, params, batch, max_len):
    """Consume a prompt; return (primed caches, last-position logits)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    parts = []
    if embeds is not None:
        parts.append(embeds.astype(compute_dtype))
    if tokens is not None:
        parts.append(embed_tokens(cfg, params["embed"]["table"], tokens,
                                  compute_dtype))
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    window = cfg.sliding_window
    fam = cfg.family
    caches = {}
    rope = rope_tables_for(cfg, S)
    if fam in ("dense", "vlm", "moe"):
        def body(h, lyr):
            return _attn_prefill(cfg, lyr, h, positions, max_len,
                                 compute_dtype, window, rope)
        if fam == "moe" and "dense_layers" in params:
            x, dc = jax.lax.scan(body, x, params["dense_layers"])
            caches["dense_layers"] = dc
        x, lc = jax.lax.scan(body, x, params["layers"])
        caches["layers"] = lc
    elif fam == "ssm":
        def body(h, lyr):
            return _mamba_prefill(cfg, lyr, h)
        x, lc = jax.lax.scan(body, x, params["layers"])
        caches["layers"] = lc
    elif fam == "hybrid":
        shared = params["shared_attn"]
        def mbody(h, lyr):
            return _mamba_prefill(cfg, lyr, h)
        def gbody(h, glyr):
            h, mc = jax.lax.scan(mbody, h, glyr)
            h, ac = _attn_prefill(cfg, shared, h, positions, max_len,
                                  compute_dtype, window, rope)
            return h, (mc, ac)
        x, (gmc, gac) = jax.lax.scan(gbody, x, params["groups"])
        caches["groups"] = gmc
        caches["shared_attn"] = gac
        if "tail" in params:
            x, tc = jax.lax.scan(mbody, x, params["tail"])
            caches["tail"] = tc
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    pv = padded_vocab(cfg)
    logits = lm_logits(cfg, params, x[:, -1:], pv)
    return caches, logits[:, 0, : cfg.vocab_size]


def _attn_decode_block(cfg, p, x, cache, pos):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.mla:
        out, c2 = mla.mla_decode(cfg, p["attn"], h, cache, pos)
    else:
        out, c2 = attn.decode_attention(cfg, p["attn"], h, cache, pos)
    x = x + out
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        y, _ = moe_mod.moe_apply(cfg, p["moe"], h)
    else:
        y = mlp_apply(cfg, p["mlp"], h)
    return x + y, c2


def _mamba_decode_block(cfg, p, x, cache):
    step = mamba.mamba1_decode if cfg.ssm.version == 1 else mamba.mamba2_decode
    out, c2 = step(cfg, p, x, cache)
    return x + out, c2


def lm_decode(cfg, params, caches, tokens, pos):
    """One decode step. tokens [B,1], pos scalar int32. Returns
    (logits [B, vocab], new caches)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    x = embed_tokens(cfg, params["embed"]["table"], tokens, compute_dtype)
    fam = cfg.family
    new_caches = {}
    if fam in ("dense", "vlm", "moe"):
        def body(h, xs):
            lyr, c = xs
            return _attn_decode_block(cfg, lyr, h, c, pos)
        if fam == "moe" and "dense_layers" in params:
            x, dc = jax.lax.scan(body, x, (params["dense_layers"],
                                           caches["dense_layers"]))
            new_caches["dense_layers"] = dc
        x, lc = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        new_caches["layers"] = lc
    elif fam == "ssm":
        def body(h, xs):
            lyr, c = xs
            return _mamba_decode_block(cfg, lyr, h, c)
        x, lc = jax.lax.scan(body, x, (params["layers"], caches["layers"]))
        new_caches["layers"] = lc
    elif fam == "hybrid":
        shared = params["shared_attn"]
        def mbody(h, xs):
            lyr, c = xs
            return _mamba_decode_block(cfg, lyr, h, c)
        def gbody(h, xs):
            glyr, gmc, gac = xs
            h, mc = jax.lax.scan(mbody, h, (glyr, gmc))
            h, ac = _attn_decode_block(cfg, shared, h, gac, pos)
            return h, (mc, ac)
        x, (gmc, gac) = jax.lax.scan(
            gbody, x, (params["groups"], caches["groups"], caches["shared_attn"]))
        new_caches["groups"] = gmc
        new_caches["shared_attn"] = gac
        if "tail" in params:
            x, tc = jax.lax.scan(mbody, x, (params["tail"], caches["tail"]))
            new_caches["tail"] = tc
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    pv = padded_vocab(cfg)
    logits = lm_logits(cfg, params, x, pv)
    return logits[:, 0, : cfg.vocab_size], new_caches
