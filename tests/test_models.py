"""Model correctness: per-arch smoke steps, causality, attention equivalences,
prefill/decode consistency, mamba chunking invariance."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.data import synthetic_batch
from repro.models import build_model
from repro.train.step import build_train_step


@pytest.mark.parametrize("arch", C.ARCHS + C.EXTRA)
def test_smoke_forward_one_train_step(arch):
    """Assigned-arch requirement: reduced config, one train step on CPU,
    output shapes + no NaNs."""
    cfg = C.get_smoke(arch).replace(attention_chunk=32)
    init_state, train_step = build_train_step(cfg)
    state = jax.jit(init_state)(jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, 2, 64, 0)
    state2, metrics = jax.jit(train_step)(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and 0 < loss < 20
    assert float(metrics["grad_norm"]) > 0
    assert int(state2.step) == 1
    # params changed (exact compare: warmup lr is tiny on purpose)
    changed = any(
        not np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree_util.tree_leaves(state.params),
                        jax.tree_util.tree_leaves(state2.params)))
    assert changed


@pytest.mark.parametrize("arch", ["granite-3-2b", "falcon-mamba-7b",
                                  "zamba2-7b", "mixtral-8x7b"])
def test_causality(arch):
    """Perturbing a future token must not change past logits."""
    cfg = C.get_smoke(arch).replace(
        attention_impl="naive", dtype="float32", param_dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    S = 16
    b = synthetic_batch(cfg, 1, S, 0)
    from repro.models.transformer import lm_forward
    h1, _ = jax.jit(lambda p, t: lm_forward(cfg, p, t))(params, b["tokens"])
    t2 = np.array(b["tokens"])
    t2[0, -1] = (t2[0, -1] + 7) % cfg.vocab_size
    h2, _ = jax.jit(lambda p, t: lm_forward(cfg, p, t))(params, t2)
    np.testing.assert_allclose(np.asarray(h1[0, : S - 1]),
                               np.asarray(h2[0, : S - 1]), atol=1e-5)
    assert not np.allclose(np.asarray(h1[0, -1]), np.asarray(h2[0, -1]))


def test_gqa_equals_mha_when_kv_equals_heads():
    cfg = C.get_smoke("granite-3-2b").replace(
        num_kv_heads=4, attention_impl="naive", dtype="float32",
        param_dtype="float32")
    from repro.models import attention as A
    from repro.models.params import init_params
    spec = A.attn_spec(cfg)
    p = init_params(spec, jax.random.PRNGKey(1), "float32")
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    out = A.self_attention(cfg, p, x, pos)
    # reference: dense softmax attention built by hand
    hd = cfg.resolved_head_dim()
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dnh->bsnh", x, p["wk"])
    v = jnp.einsum("bsd,dnh->bsnh", x, p["wv"])
    from repro.models.layers import apply_rope
    q = apply_rope(q, pos[:, :, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, :, None], cfg.rope_theta)
    s = jnp.einsum("bqnh,bknh->bnqk", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((8, 8), bool))
    s = jnp.where(mask[None, None], s, -1e30)
    o = jnp.einsum("bnqk,bknh->bqnh", jax.nn.softmax(s, -1), v)
    ref = jnp.einsum("bqnh,nhd->bqd", o, p["wo"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("arch", ["granite-3-2b", "gemma-2b", "qwen3-14b",
                                  "mixtral-8x7b", "deepseek-v3-671b"])
def test_chunked_equals_naive_attention(arch):
    cfg_n = C.get_smoke(arch).replace(
        attention_impl="naive", dtype="float32", param_dtype="float32")
    cfg_c = cfg_n.replace(attention_impl="chunked", attention_chunk=16)
    mn, mc = build_model(cfg_n), build_model(cfg_c)
    params = mn.init(jax.random.PRNGKey(0))
    b = synthetic_batch(cfg_n, 2, 40, 0)
    ln, _ = jax.jit(mn.loss)(params, b)
    lc, _ = jax.jit(mc.loss)(params, b)
    assert abs(float(ln) - float(lc)) < 1e-5


@pytest.mark.parametrize("arch", ["granite-3-2b", "mixtral-8x7b",
                                  "falcon-mamba-7b", "zamba2-7b",
                                  "deepseek-v3-671b", "seamless-m4t-large-v2",
                                  "llava-next-mistral-7b"])
def test_decode_matches_prefill(arch):
    """Greedy continuation invariance: decode(prefill(x), t) == prefill(x+t)."""
    cfg = C.get_smoke(arch).replace(
        attention_impl="naive", dtype="float32", param_dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    S = 24
    batch = synthetic_batch(cfg, 2, 2 * S if cfg.family == "audio" else S, 0)
    caches, _ = jax.jit(lambda p, b: m.prefill(p, b, S + 8))(params, batch)
    tok = jnp.full((2, 1), 7, jnp.int32)
    logits_d, _ = jax.jit(m.decode)(params, caches, tok,
                                    jnp.asarray(S, jnp.int32))
    b2 = dict(batch)
    key = {"audio": "dec_tokens"}.get(cfg.family, "tokens")
    b2[key] = np.concatenate([batch[key], np.full((2, 1), 7, np.int32)], 1)
    _, logits_p2 = jax.jit(lambda p, b: m.prefill(p, b, S + 9))(params, b2)
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_p2),
                               atol=2e-3)


def test_sliding_window_bounds_cache():
    cfg = C.get_smoke("mixtral-8x7b")
    m = build_model(cfg)
    spec = m.cache_spec(2, 10_000)
    # SWA ring cache: bounded by window (32 in smoke), not 10k
    assert spec["layers"]["k"].shape[2] == cfg.sliding_window


def test_mamba_chunk_size_invariance():
    """The chunked scan must not depend on chunk size."""
    base = C.get_smoke("falcon-mamba-7b").replace(dtype="float32",
                                                  param_dtype="float32")
    m = build_model(base)
    params = m.init(jax.random.PRNGKey(0))
    b = synthetic_batch(base, 2, 48, 0)
    losses = []
    for q in (4, 16, 48):
        cfg = base.replace(ssm=dataclasses.replace(base.ssm, chunk=q))
        losses.append(float(jax.jit(build_model(cfg).loss)(params, b)[0]))
    assert max(losses) - min(losses) < 1e-4, losses


def test_mamba2_chunk_size_invariance():
    base = C.get_smoke("zamba2-7b").replace(dtype="float32",
                                            param_dtype="float32")
    m = build_model(base)
    params = m.init(jax.random.PRNGKey(0))
    b = synthetic_batch(base, 2, 48, 0)
    losses = []
    for q in (8, 16, 48):
        cfg = base.replace(ssm=dataclasses.replace(base.ssm, chunk=q))
        losses.append(float(jax.jit(build_model(cfg).loss)(params, b)[0]))
    assert max(losses) - min(losses) < 1e-4, losses


def test_moe_routing_properties():
    from repro.models.moe import _route
    cfg = C.get_smoke("mixtral-8x7b")
    x = jax.random.normal(jax.random.PRNGKey(0), (64, cfg.d_model))
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (cfg.d_model, cfg.moe.num_experts)) * 0.1
    weights, ids, aux = _route(cfg, w, x)
    assert weights.shape == (64, cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-5)
    assert (np.asarray(ids) < cfg.moe.num_experts).all()
    # distinct experts per token
    for row in np.asarray(ids):
        assert len(set(row.tolist())) == cfg.moe.top_k
    assert float(aux) >= 1.0 - 1e-6   # Switch aux loss lower bound at balance


def test_moe_skewed_router_drops_nothing():
    """Dropless: a router skewed so that one held expert takes most rows
    still computes every pair routed to a held expert (``moe_rows``), and
    the layer's output equals every held expert applied densely."""
    from repro.models.moe import moe_apply
    cfg = C.get_smoke("moonlight-16b-a3b").replace(dtype="float32",
                                                   param_dtype="float32")
    mo = cfg.moe
    params = build_model(cfg).init(jax.random.PRNGKey(1))
    p = jax.tree_util.tree_map(lambda x: x[0], params["layers"])["moe"]
    hot = mo.expert_offset + 2                   # a held expert
    p["router_bias"] = p["router_bias"].at[hot].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, cfg.d_model))
    y, m = jax.jit(lambda p, x: moe_apply(cfg, p, x))(p, x)

    xf = x.reshape(-1, cfg.d_model)
    s = jax.nn.sigmoid(xf @ p["router"])
    _, ids = jax.lax.top_k(s + p["router_bias"], mo.top_k)
    ids = np.asarray(ids)
    local = ids - mo.expert_offset
    held = (local >= 0) & (local < mo.held())
    rows = np.bincount(local[held], minlength=mo.held())
    assert (ids == hot).sum() == xf.shape[0]     # every token picks it
    assert rows[2] == xf.shape[0] and rows.max() > 3 * rows.mean()
    assert float(m["moe_rows"]) == held.sum()
    np.testing.assert_allclose(float(m["moe_load_max"]),
                               rows.max() / rows.mean(), rtol=1e-6)

    w = np.asarray(jnp.take_along_axis(s, jnp.asarray(ids), -1))
    w = w / w.sum(-1, keepdims=True) * mo.routed_scaling
    e = p["experts"]
    dense = jnp.einsum("td,edf->tef", xf, e["wi"])
    act = jax.nn.silu(jnp.einsum("td,edf->tef", xf, e["wg"])) * dense
    out_e = jnp.einsum("tef,efd->ted", act, e["wo"])       # [T, held, d]
    gate = np.zeros((xf.shape[0], mo.held()), np.float32)
    for t, j in zip(*np.nonzero(held)):
        gate[t, local[t, j]] += w[t, j]
    sh = p["shared"]
    shared = (jax.nn.silu(xf @ sh["wg"]) * (xf @ sh["wi"])) @ sh["wo"]
    ref = jnp.einsum("te,ted->td", gate, out_e) + shared
    np.testing.assert_allclose(np.asarray(y).reshape(ref.shape),
                               np.asarray(ref), rtol=2e-4, atol=2e-5)


def test_vlm_loss_masks_image_prefix():
    cfg = C.get_smoke("llava-next-mistral-7b").replace(dtype="float32",
                                                       param_dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = synthetic_batch(cfg, 2, 32, 0)
    assert b["embeds"].shape[1] == cfg.frontend_tokens
    loss, _ = jax.jit(m.loss)(params, b)
    assert np.isfinite(float(loss))


def test_seq_shard_loss_invariance():
    """seq_shard is a pure layout knob: identical results on one device."""
    cfg = C.get_smoke("qwen3-14b").replace(dtype="float32",
                                           param_dtype="float32",
                                           seq_shard=False)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = synthetic_batch(cfg, 2, 64, 0)
    l1, _ = jax.jit(m.loss)(params, b)
    l2, _ = jax.jit(build_model(cfg.replace(seq_shard=True)).loss)(params, b)
    assert abs(float(l1) - float(l2)) < 1e-6


def test_dense_layout_dp_loss_invariance():
    """dense_layout only changes sharding axes, never math."""
    cfg = C.get_smoke("deepseek-v3-671b").replace(
        dtype="float32", param_dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = synthetic_batch(cfg, 2, 32, 0)
    l1, _ = jax.jit(m.loss)(params, b)
    m2 = build_model(cfg.replace(dense_layout="dp"))
    l2, _ = jax.jit(m2.loss)(params, b)
    assert abs(float(l1) - float(l2)) < 1e-6


def test_attention_remat_chunk_invariance():
    cfg = C.get_smoke("granite-3-2b").replace(
        dtype="float32", param_dtype="float32", attention_impl="chunked",
        attention_chunk=16)
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    b = synthetic_batch(cfg, 2, 48, 0)
    l1, _ = jax.jit(build_model(cfg.replace(attention_remat_chunk=False)).loss)(params, b)
    l2, _ = jax.jit(build_model(cfg.replace(attention_remat_chunk=True)).loss)(params, b)
    assert abs(float(l1) - float(l2)) < 1e-6
