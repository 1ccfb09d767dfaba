"""Plain reference of a dense decoder-only LM and its first training steps.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: token embedding, then per layer RMSNorm, grouped-query
attention with split-half rotary positions and a causal softmax, an output
projection, RMSNorm, and a GELU (tanh form) or SwiGLU MLP, each with a
residual; a final RMSNorm and logits through the tied embedding. The loss is
the mean next-token cross-entropy over every position of the batch. Training
is global-norm clipping and AdamW (decay on every leaf of rank two or more,
as stored) under a linear warm-up.

It imports nothing of the program and takes nothing the program made: its
weights come from the benchmark's own initializer and its tokens from the
benchmark's generator. The layers run one at a time, a ``lax.scan`` over
the stacked layer weights under ``jax.checkpoint``, and the batch in blocks
of rows, so it compiles one layer body and fits on one chip.

``precision="fp8"`` is the control: every matrix product takes operands
rounded to float8 e4m3 with a per-tensor scale (straight-through in the
backward pass), the precision step below the bfloat16 the configuration
computes in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0          # largest finite float8_e4m3fn


def _round_fp8(x):
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / F8_MAX)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, precision):
    if precision == "fp8":
        a, b = _round_fp8(a), _round_fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, theta):
    """Split-half rotary positions over [b, s, heads, hd]."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2.0 / hd)
    ang = np.arange(s, dtype=np.float32)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(dims, precision, x, p):
    b, s, _ = x.shape
    h_n, kv, hd = dims["num_heads"], dims["num_kv_heads"], dims["head_dim"]
    eps = dims["norm_eps"]
    h = _rms(x, p["ln1"], eps)
    q = _rope(_mm("bsd,dnh->bsnh", h, p["attn"]["wq"], precision),
              dims["rope_theta"])
    k = _rope(_mm("bsd,dnh->bsnh", h, p["attn"]["wk"], precision),
              dims["rope_theta"])
    v = _mm("bsd,dnh->bsnh", h, p["attn"]["wv"], precision)
    rep = h_n // kv                        # query head i reads kv head i // rep
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = _mm("bqnh,bknh->bnqk", q, k, precision) / math.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = _mm("bnqk,bknh->bqnh", w, v, precision)
    x = x + _mm("bqnh,nhd->bqd", o, p["attn"]["wo"], precision)
    h = _rms(x, p["ln2"], eps)
    up = _mm("bsd,df->bsf", h, p["mlp"]["wi"], precision)
    if dims["gated"]:
        g = _mm("bsd,df->bsf", h, p["mlp"]["wg"], precision)
        act = jax.nn.sigmoid(g) * g * up
    else:
        act = _gelu_tanh(up)
    return x + _mm("bsf,fd->bsd", act, p["mlp"]["wo"], precision)


def nll_sum(dims, precision, train, frozen, tokens):
    """Summed next-token negative log-likelihood of a block of rows.
    ``frozen`` holds the embedding and the lower layers (or is empty),
    ``train`` the layers above them and the final norm (and, with nothing
    frozen, the embedding); no gradient reaches the frozen layers, so they
    run forward only."""
    table = (frozen or train)["embed"]["table"]
    x = jnp.take(table, tokens, axis=0)
    layer = jax.checkpoint(lambda x, lp: (_layer(dims, precision, x, lp),
                                          None))
    for stack in ([frozen["layers"]] if frozen else []) + [train["layers"]]:
        x, _ = jax.lax.scan(layer, x, stack)
    x = _rms(x, train["ln_f"], dims["norm_eps"])[:, :-1]
    logits = _mm("bsd,vd->bsv", x, table[: dims["vocab_size"]], precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(lse - gold)


def lr_at(step: int, peak: float, warmup: int) -> float:
    """Linear warm-up; the first steps never reach the cosine phase."""
    if step + 1 >= warmup:
        raise ValueError("the reference covers the warm-up steps only")
    return peak * (step + 1) / warmup


def _norms(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(v, np.float64).ravel())) for p, v in flat}


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
