"""The dropless expert layer and what it is built from, at smoke size on the
CPU: the grouped-matmul kernels in interpret mode against their jnp oracles
(forward, input gradient, weight gradient), the tile-aligned layout, the
sigmoid router with a selection bias against a hand-written case, the held
share against the uncut layer and on a mesh, and MLA with a direct query
projection (training forward against the absorbed decode)."""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.data import synthetic_batch
from repro.kernels import ops
from repro.kernels.gmm import expert_gmm, expert_tgmm
from repro.kernels.ref import gmm_ref, tgmm_ref
from repro.models import build_model
from repro.models.moe import _layout, _route, moe_apply

TM, K, N = 16, 32, 24
ROWS = (20, 0, 9, 33)          # rows per expert; the second has none


def _case(key=0):
    """A tile-aligned layout of ROWS with random operands; rows past the
    last tile and the padding rows of each group's last tile are zero."""
    G = len(ROWS)
    tiles = [-(-r // TM) for r in ROWS]
    nt = sum(tiles)
    M = (nt + 2) * TM
    tg = np.repeat(np.arange(G), tiles)
    tg = np.concatenate([tg, np.full(M // TM - nt, G - 1)]).astype(np.int32)
    valid = np.zeros(M, bool)
    start = 0
    for r, t in zip(ROWS, tiles):
        valid[start:start + r] = True
        start += t * TM
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(key), 3)
    lhs = jax.random.normal(k1, (M, K)) * valid[:, None]
    rhs = jax.random.normal(k2, (G, K, N))
    dout = jax.random.normal(k3, (M, N)) * valid[:, None]
    return lhs, rhs, dout, jnp.asarray(tg), jnp.int32(nt), valid


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_expert_gmm_matches_the_oracle(transpose_rhs):
    lhs, rhs, dout, tg, nt, valid = _case()
    a, w = (dout, rhs) if transpose_rhs else (lhs, rhs)
    got = expert_gmm(a, w, tg, nt, tm=TM, transpose_rhs=transpose_rhs,
                     interpret=True)
    want = gmm_ref(a, w, tg, nt, TM, transpose_rhs=transpose_rhs)
    rows = int(nt) * TM                 # rows past the last tile: unwritten
    np.testing.assert_allclose(np.asarray(got)[:rows],
                               np.asarray(want)[:rows], rtol=1e-5, atol=1e-5)


def test_expert_tgmm_matches_the_oracle_and_zeroes_empty_groups():
    lhs, rhs, dout, tg, nt, _ = _case(1)
    tiles = jnp.asarray([-(-r // TM) for r in ROWS])
    got = expert_tgmm(lhs, dout, tg, nt, tiles, num_groups=len(ROWS), tm=TM,
                      interpret=True)
    want = tgmm_ref(lhs, dout, tg, nt, TM, len(ROWS))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-4)
    assert not np.asarray(got)[1].any()


def test_expert_matmul_gradients_match_the_oracle():
    lhs, rhs, dout, tg, nt, valid = _case(2)

    def loss(mm, a, w):
        return jnp.sum(jnp.where(valid[:, None], mm(a, w), 0.0) * dout)
    kern = jax.grad(lambda a, w: loss(lambda a, w: ops.expert_matmul_pallas(
        a, w, tg, nt, TM, True), a, w), argnums=(0, 1))(lhs, rhs)
    oracle = jax.grad(lambda a, w: loss(lambda a, w: gmm_ref(
        a, w, tg, nt, TM), a, w), argnums=(0, 1))(lhs, rhs)
    rows = int(nt) * TM
    np.testing.assert_allclose(np.asarray(kern[0])[:rows],
                               np.asarray(oracle[0])[:rows], rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(kern[1]), np.asarray(oracle[1]),
                               rtol=1e-5, atol=1e-4)


def test_layout_aligns_each_expert_to_a_tile():
    local = jnp.asarray([2, 0, 5, 2, -1, 2, 0, 3])   # 5, -1: not held
    row, tg, nt, rows, M = _layout(local, 4, 4)
    assert list(np.asarray(rows)) == [2, 0, 3, 1]
    assert int(nt) == 3 and M == (2 + 4) * 4
    assert list(np.asarray(row)) == [4, 0, M, 5, M, 6, 1, 8]
    assert list(np.asarray(tg)[:3]) == [0, 2, 3]


def test_sigmoid_router_selects_on_bias_and_scales_normalised_gates():
    cfg = C.get_smoke("moonlight-16b-a3b")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, num_experts=6,
                                              top_k=2))
    logits = np.array([[2.0, 1.0, 0.5, -1.0, 0.0, 3.0]], np.float32)
    bias = np.array([0.0, 0.0, 0.7, 0.0, 0.0, -0.9], np.float32)
    # identity router: the logits are the token itself
    w, ids, _ = _route(cfg, jnp.eye(6), jnp.asarray(logits), jnp.asarray(bias))
    s = 1 / (1 + np.exp(-logits[0]))
    # s + bias = [.881, .731, 1.322, .269, .5, .053]: experts 2 and 0 win,
    # though expert 5 has the largest score
    assert sorted(np.asarray(ids)[0].tolist()) == [0, 2]
    gate = {e: 2.446 * s[e] / (s[0] + s[2]) for e in (0, 2)}
    for j, e in enumerate(np.asarray(ids)[0]):
        assert float(w[0, j]) == pytest.approx(gate[int(e)], rel=1e-6)


def test_held_shares_add_up_to_the_uncut_layer():
    """Guide section 4's share test: over every offset, the eight shares of
    one MoE layer, with the shared experts counted once, sum to the layer
    that holds every expert."""
    full = C.get_smoke("moonlight-16b-a3b").replace(dtype="float32",
                                                    param_dtype="float32")
    full = full.replace(moe=dataclasses.replace(full.moe, expert_offset=0,
                                                experts_held=None))
    mo = full.moe
    params = build_model(full).init(jax.random.PRNGKey(3))
    p = jax.tree_util.tree_map(lambda x: x[0], params["layers"])["moe"]
    p["router_bias"] = jax.random.normal(jax.random.PRNGKey(4),
                                         p["router_bias"].shape)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, full.d_model))
    whole, m = moe_apply(full, p, x)
    shares, per = 8, mo.num_experts // 8
    routed_only = {k: v for k, v in p.items() if k != "shared"}
    total, rows = 0.0, 0.0
    for s in range(shares):
        cfg = full.replace(moe=dataclasses.replace(
            mo, expert_offset=s * per, experts_held=per))
        ps = dict(routed_only, experts=jax.tree_util.tree_map(
            lambda w: w[s * per:(s + 1) * per], p["experts"]))
        y, ms = moe_apply(cfg, ps, x)
        total, rows = total + y, rows + float(ms["moe_rows"])
    from repro.models.layers import mlp_apply
    total = total + mlp_apply(full, p["shared"], x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    assert rows == float(m["moe_rows"]) == x.shape[0] * x.shape[1] * mo.top_k


def test_mla_direct_query_decode_matches_the_training_forward():
    """MLA with ``q_lora_rank=None``: decoding through the absorbed latent
    cache gives the logits of the training-path forward (prefill) over the
    longer prompt."""
    cfg = C.get_smoke("moonlight-16b-a3b").replace(
        dtype="float32", param_dtype="float32", attention_impl="naive")
    assert cfg.mla.q_lora_rank is None
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(1))
    assert "w_q" in params["layers"]["attn"]
    assert "w_dq" not in params["layers"]["attn"]
    S = 24
    batch = synthetic_batch(cfg, 2, S, 0)
    caches, _ = jax.jit(lambda p, b: m.prefill(p, b, S + 8))(params, batch)
    tok = jnp.full((2, 1), 7, jnp.int32)
    logits_d, _ = jax.jit(m.decode)(params, caches, tok,
                                    jnp.asarray(S, jnp.int32))
    b2 = dict(batch, tokens=np.concatenate(
        [batch["tokens"], np.full((2, 1), 7, np.int32)], 1))
    _, logits_p = jax.jit(lambda p, b: m.prefill(p, b, S + 9))(params, b2)
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_p),
                               atol=2e-3)


def test_param_count_follows_the_held_share():
    cfg = C.get("moonlight-16b-a3b")
    assert abs(cfg.param_count() - 15.96e9) / 15.96e9 < 0.01
    share = cfg.replace(num_layers=9, vocab_size=20480,
                        moe=dataclasses.replace(cfg.moe, experts_held=8))
    shapes = build_model(share).param_shapes()
    assert share.param_count() == sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)) \
        == 970_107_904


def test_shard_map_path_computes_the_held_share():
    """On 8 fake devices the layer's shard_map path (expert-parallel for
    Moonlight's 8 held experts over a 4-way model axis, tensor-parallel for
    Mixtral's 4 experts over an 8-way one) gives the single-device result
    and counters."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        import repro.configs as C
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.models.moe import moe_apply
        from repro.parallel import use_mesh

        for arch, shape in (("moonlight-16b-a3b", (2, 4)),
                            ("mixtral-8x7b", (1, 8))):
            cfg = C.get_smoke(arch).replace(dtype="float32",
                                            param_dtype="float32")
            params = build_model(cfg).init(jax.random.PRNGKey(0))
            p = jax.tree_util.tree_map(lambda x: x[0], params["layers"])["moe"]
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
            f = jax.jit(lambda p, x: moe_apply(cfg, p, x))
            y0, m0 = f(p, x)
            mesh = make_mesh(shape, ("data", "model"))
            with mesh, use_mesh(mesh):
                y1, m1 = jax.jit(lambda p, x: moe_apply(cfg, p, x))(p, x)
            np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                                       rtol=1e-5, atol=1e-5)
            for k in ("moe_rows", "moe_load_max"):
                assert abs(float(m1[k]) - float(m0[k])) < 1e-4, (arch, k)
        print("SHARDED_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert "SHARDED_OK" in out.stdout, out.stderr[-3000:]
