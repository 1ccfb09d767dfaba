"""Plain reference of an MLA and mixture-of-experts decoder (the DeepSeek-V3
layout) and its first training steps.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision. Token embedding; then the leading dense layers and
the MoE layers, each RMSNorm, multi-head latent attention, RMSNorm, and a
SwiGLU MLP (dense layers) or the expert layer, each with a residual; a
final RMSNorm and logits through the untied head. The loss is the mean
next-token cross-entropy over every position of the batch. Training is
global-norm clipping and AdamW (decay on every leaf of rank two or more, as
stored) under a linear warm-up, as ``references/dense_lm.py``.

Attention: queries from a direct projection (``q_lora_rank`` null) or a
normed query latent; a normed key-value latent expanded to per-head keys
and values, and one rotary key shared by the heads; split-half rotary
positions on the 64 rope dimensions; scores over ``sqrt(qk_nope +
qk_rope)``; a causal softmax, computed in blocks of ``QUERY_BLOCK`` queries
so that long sequences fit.

Expert layer: the router's sigmoid scores over all experts; the top
``top_k`` by score plus bias; gates the selected scores, normalised and
scaled. Every held expert is evaluated on every token, densely, and its
output weighted by the gate the token gives it (zero where the token did
not select it); the experts this chip does not hold give nothing. Shared
experts are one SwiGLU of their summed width.

It imports nothing of the program and takes nothing the program made.
``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 with a per-tensor scale, the precision step below
the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from harness.spec import load_module

_dense = load_module("references", "dense_lm")
_mm, _rms, _rope, _norms, lr_at = (_dense._mm, _dense._rms, _dense._rope,
                                   _dense._norms, _dense.lr_at)

QUERY_BLOCK = 1024


def _swiglu(precision, h, p):
    up = _mm("bsd,df->bsf", h, p["wi"], precision)
    g = _mm("bsd,df->bsf", h, p["wg"], precision)
    return _mm("bsf,fd->bsd", jax.nn.sigmoid(g) * g * up, p["wo"], precision)


def _attention(dims, precision, h, p):
    b, s, _ = h.shape
    nope, rope = dims["qk_nope_head_dim"], dims["qk_rope_head_dim"]
    theta, eps = dims["rope_theta"], dims["norm_eps"]
    if "w_q" in p:
        q = _mm("bsd,dnh->bsnh", h, p["w_q"], precision)
    else:
        cq = _rms(_mm("bsd,dr->bsr", h, p["w_dq"], precision), p["q_ln"], eps)
        q = _mm("bsr,rnh->bsnh", cq, p["w_uq"], precision)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
    c = _rms(_mm("bsd,dr->bsr", h, p["w_dkv"], precision), p["kv_ln"], eps)
    k_rope = _rope(_mm("bsd,dr->bsr", h, p["w_kr"], precision)[:, :, None],
                   theta)[:, :, 0]
    k_nope = _mm("bsr,rnh->bsnh", c, p["w_uk"], precision)
    v = _mm("bsr,rnh->bsnh", c, p["w_uv"], precision)
    qb = min(QUERY_BLOCK, s)
    if s % qb:
        raise ValueError(f"sequence {s} is not a whole number of "
                         f"{qb}-query blocks")
    scale = 1.0 / math.sqrt(nope + rope)

    def block(_, i):
        rows = lambda x: jax.lax.dynamic_slice_in_dim(x, i * qb, qb, 1)  # noqa: E731
        sc = (_mm("bqnh,bknh->bnqk", rows(q_nope), k_nope, precision)
              + _mm("bqnh,bkh->bnqk", rows(q_rope), k_rope, precision)) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(s)[None, :]
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        return None, _mm("bnqk,bknh->bqnh", w, v, precision)
    _, o = jax.lax.scan(jax.checkpoint(block), None, jnp.arange(s // qb))
    o = jnp.moveaxis(o, 0, 1).reshape(b, s, *o.shape[3:])
    return _mm("bqnh,nhd->bqd", o, p["wo"], precision)


def _experts(dims, precision, h, p):
    """The held experts' part of the expert layer, plus the shared ones."""
    scores = jax.nn.sigmoid(_mm("bsd,de->bse", h, p["router"], precision))
    _, ids = jax.lax.top_k(scores + p["router_bias"], dims["top_k"])
    sel = jnp.take_along_axis(scores, ids, axis=-1)
    gate = sel / jnp.sum(sel, axis=-1, keepdims=True) * dims["routed_scaling"]
    # gate of each held expert; a selected expert not held gives nothing
    held = jax.nn.one_hot(ids - dims["offset"], dims["held"])   # [b,s,k,E]
    gate = jnp.sum(gate[..., None] * held, axis=2)
    e = p["experts"]
    up = _mm("bsd,edf->bsef", h, e["wi"], precision)
    g = _mm("bsd,edf->bsef", h, e["wg"], precision)
    out = _mm("bsef,efd->bsed", jax.nn.sigmoid(g) * g * up, e["wo"], precision)
    return _mm("bse,bsed->bsd", gate, out, precision) \
        + _swiglu(precision, h, p["shared"])


def _layer(dims, precision, mlp, x, p):
    eps = dims["norm_eps"]
    x = x + _attention(dims, precision, _rms(x, p["ln1"], eps), p["attn"])
    return x + mlp(dims, precision, _rms(x, p["ln2"], eps), p)


def _params(frozen, train):
    """The whole parameter tree: the trained experts are the first of each
    MoE layer's held experts."""
    if not frozen:
        return train
    moe = frozen["layers"]["moe"]
    experts = jax.tree_util.tree_map(
        lambda t, f: jnp.concatenate([t, f], axis=1), train["experts"],
        moe["experts"])
    return dict(frozen, layers=dict(frozen["layers"],
                                    moe=dict(moe, experts=experts)))


def nll_sum(dims, precision, train, frozen, tokens):
    """Summed next-token negative log-likelihood of a block of rows; no
    gradient reaches the frozen part, and the dense layers below the
    trained experts run forward only."""
    p = _params(frozen, train)
    x = jnp.take(p["embed"]["table"], tokens, axis=0)
    dense = jax.checkpoint(lambda x, lp: (_layer(
        dims, precision, lambda d, pr, h, q: _swiglu(pr, h, q["mlp"]), x, lp),
        None))
    moe = jax.checkpoint(lambda x, lp: (_layer(
        dims, precision, lambda d, pr, h, q: _experts(d, pr, h, q["moe"]),
        x, lp), None))
    if "dense_layers" in p:
        x, _ = jax.lax.scan(dense, x, p["dense_layers"])
    x, _ = jax.lax.scan(moe, x, p["layers"])
    x = _rms(x, p["ln_f"], dims["norm_eps"])[:, :-1]
    logits = _mm("bsd,dv->bsv", x, p["unembed"]["table"][:, :dims["vocab_size"]],
                 precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(lse - gold)


def first_steps(dims: dict, opt: dict, frozen, train, batches,
                block_rows: int, precision: str = "highest") -> dict:
    """Train ``train`` (with ``frozen`` held fixed) for ``len(batches)``
    steps. Returns the loss of each step, per-leaf norms of the first
    clipped gradient and of the parameters' change over all the steps."""
    grad_fn = jax.jit(jax.value_and_grad(
        lambda t, f, tok: nll_sum(dims, precision, t, f, tok)))
    tmap = jax.tree_util.tree_map
    p0 = tmap(np.asarray, jax.device_get(train))
    mu = tmap(jnp.zeros_like, train)
    nu = tmap(jnp.zeros_like, train)
    losses, g1 = [], None
    for step, tokens in enumerate(batches):
        rows, seq = tokens.shape
        count = rows * (seq - 1)
        total, grads = 0.0, tmap(jnp.zeros_like, train)
        for r in range(0, rows, block_rows):
            s, g = grad_fn(train, frozen, jnp.asarray(tokens[r:r + block_rows]))
            total += float(s)
            grads = tmap(jnp.add, grads, g)
        grads = tmap(lambda g: g / count, grads)
        losses.append(total / count)
        gn = math.sqrt(sum(float(jnp.sum(g * g))
                           for g in jax.tree_util.tree_leaves(grads)))
        scale = min(1.0, opt["grad_clip"] / max(gn, 1e-9))
        grads = tmap(lambda g: g * scale, grads)
        if g1 is None:
            g1 = _norms(grads)
        t = step + 1
        lr = lr_at(step, opt["peak_lr"], opt["warmup"])
        b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
        mu = tmap(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

        def update(p, m, v):
            u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
            if p.ndim >= 2:
                u = u + wd * p
            return p - lr * u
        train = tmap(update, train, mu, nu)
    delta = tmap(lambda a, b: np.asarray(a, np.float64) - b,
                 jax.device_get(train), p0)
    return {"losses": losses, "grad_norms": g1, "delta_norms": _norms(delta)}
