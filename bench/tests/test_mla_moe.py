"""The ``mla_moe`` family and reference (Moonlight-16B-A3B's cell) at a size
a test can hold: the program's step against the plain reference through
``run_cell``, the float8 control refused, the ESFT split, the FLOP counts
by hand, and the two per-layer metrics' readers."""
import json
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest
from tiny_moe import tiny_moe_cell

from harness.runner import run_cell
from harness.spec import BENCH, Cell, load_benchmark, load_reader

CELL = "moonlight-16b-a3b.esft_record"

# In float32 compute the program's step is the reference's mathematics:
# readings at most 4.5e-7 (loss), 3.1e-7 (grad), 5.0e-6 (update) over seeds
# 5, 6 and 2**31 + 9 in both trainable forms (CPU), so these limits leave
# about 20x for round-off, while the float8 control reads at least 5e-4,
# 0.05 and 4e-3 there and fails all three.
F32_LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-5, "update_gap": 1e-4,
              "store_mismatch": 0}
# In the configuration's bfloat16 compute, at this size a few tokens change
# experts on rounding: the program reads up to 4.7e-4 / 0.039 / 8.7e-3 over
# those seeds and 2**31 + 77, the half-batch fault at least 1.7e-3 / 0.14 /
# 0.039, so these sit between with room on both sides.
BF16_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.1, "update_gap": 0.02,
               "store_mismatch": 0}
ESFT = {"experts_per_layer": 1}


def _f32_cell(monkeypatch, trainable=None, limits=F32_LIMITS):
    cell = tiny_moe_cell(trainable, limits)
    program_config = cell.family.program_config
    monkeypatch.setattr(cell.family, "program_config",
                        lambda c: program_config(c).replace(dtype="float32"))
    return cell


@pytest.mark.parametrize("trainable", [ESFT, "all"], ids=["esft", "all"])
def test_program_step_is_the_reference_in_float32(monkeypatch, trainable,
                                                  tmp_path):
    res, lines = run_cell(_f32_cell(monkeypatch, trainable), 2**31 + 5, 0.3,
                          False, t_start=time.monotonic(), rehearsal=True,
                          run_dir=tmp_path)
    assert res["correct"] is True, lines
    assert res["window"]["stored_bytes"] == res["window"]["new_bytes_counted"]


def test_control_fails_the_float32_limits(monkeypatch, tmp_path):
    res, lines = run_cell(_f32_cell(monkeypatch), 2**31 + 5, 0.3, False,
                          t_start=time.monotonic(), control=True,
                          rehearsal=True, run_dir=tmp_path)
    assert res["correct"] is False
    for k in ("loss_gap", "grad_gap", "update_gap"):
        assert res["checks"][k]["value"] > 10 * F32_LIMITS[k], k


def test_bfloat16_rehearsal_is_correct(tmp_path):
    res, lines = run_cell(tiny_moe_cell(ESFT, BF16_LIMITS), 2**31 + 77, 0.5,
                          False, t_start=time.monotonic(), rehearsal=True,
                          run_dir=tmp_path)
    assert res["correct"] is True, lines
    assert res["window"]["steady"] and res["window"]["compiles"] == 0
    assert res["window"]["stored_bytes"] == res["window"]["new_bytes_counted"]


def test_esft_split_trains_the_first_held_expert_of_each_layer():
    cell = tiny_moe_cell()
    from harness.model import System, seed_key
    system = System(cell.family, cell.config, cell.traffic)
    state = system.init_state(seed_key(3))
    frozen, train = state["frozen"], state["train"].params
    assert set(train) == {"experts"}
    for name, leaf in train["experts"].items():
        full = frozen["layers"]["moe"]["experts"][name]
        assert leaf.shape[:2] == (2, 1) and full.shape[:2] == (2, 7)
    merged = cell.family.merge_trainable(frozen, train)
    again = cell.family.merge_trainable(*cell.family.split_trainable(merged,
                                                                     ESFT))
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the frozen router bias is the initializer's rank-one 1
    assert np.all(np.asarray(frozen["layers"]["moe"]["router_bias"]) == 1.0)


def _moonlight():
    cell = Cell.find(load_benchmark(), CELL)
    return cell, cell.family.dims(cell.config)


def test_cell_resolves_to_the_family_and_its_metrics():
    cell, d = _moonlight()
    assert cell.family.__file__.endswith("families/mla_moe.py")
    assert cell.reference.__file__.endswith("references/mla_moe.py")
    cfg = cell.family.program_config(cell.config)
    assert cfg.param_count() == 970_107_904
    assert (cfg.moe.num_experts, cfg.moe.held(), cfg.moe.top_k) == (64, 8, 6)
    assert cfg.mla.q_lora_rank is None and not cfg.tie_embeddings
    assert {m["name"] for m in cell.per_layer} == {"expert_gmm_roofline",
                                                   "mfu.esft_record"}


def test_unsupported_router_is_refused():
    cell, _ = _moonlight()
    for key, value in (("scoring_func", "softmax"), ("topk_group", 2)):
        with pytest.raises(SystemExit, match="does not compute"):
            cell.family.program_config(dict(cell.config, **{key: value}))


def test_flops_by_hand():
    cell, d = _moonlight()
    b, s = 2, 8192
    T, P = b * s, b * (s - 1)
    expert = 2 * 3 * 2048 * 1408
    proj = 2 * (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    core = 2 * 16 * 320 * (s + 1) / 2
    other = proj + core + 2 * 2048 * 64 + 2 * expert        # router, shared
    routed = 0.75 * expert                                  # 6 x 8 / 64 rows
    fwd = (proj + core + 2 * 3 * 2048 * 11264) * T \
        + 8 * (other + routed) * T + 2 * 2048 * 20480 * P
    trained = 6 / 64 * T                                    # trained rows
    experts = 8 * routed * T + 7 * routed * T \
        + trained * 2 * 2048 * 1408 + 8 * trained * expert
    assert cell.family.expert_flops(d, b, s, ESFT) == pytest.approx(experts)
    bwd = 2 * 2048 * 20480 * P + 7 * (other + core) * T \
        + experts - 8 * routed * T
    assert cell.family.step_flops(d, b, s, ESFT) == pytest.approx(fwd + bwd)
    assert cell.family.step_flops(d, b, s, "all") == pytest.approx(3 * fwd)
    assert 39e12 < fwd + bwd < 41e12


def _trace(ops):
    return SimpleNamespace(op_s={n: s for n, s, _ in ops},
                           op_count={n: c for n, _, c in ops})


def test_expert_roofline_reads_the_named_kernels():
    cell, d = _moonlight()
    read = load_reader("expert_gmm_roofline")
    steps, peak = 16, 197e12
    need = steps * cell.family.expert_flops(d, 2, 8192, ESFT)
    ops = [("record_train_step/expert_gmm.3", 0.6, 16 * 72),
           ("record_train_step/expert_tgmm.1", 0.3, 16 * 24),
           ("record_train_step/fusion.9", 5.0, 16)]
    run = SimpleNamespace(cell=cell, window=SimpleNamespace(steps=steps),
                          trace=_trace(ops), peaks={"bf16_flops": peak})
    assert read(run) == pytest.approx(100 * need / 0.9 / peak)
    # a count that is not a whole multiple of the steps reads nothing
    run.trace = _trace(ops[:1] + [("record_train_step/expert_tgmm.1", 0.3,
                                   16 * 24 + 1)])
    assert read(run) is None
    # nor does a window without the kernels, or a family without the count
    run.trace = _trace(ops[2:])
    assert read(run) is None
    run.trace = _trace(ops)
    run.cell = SimpleNamespace(family=object(), config={}, traffic={})
    assert read(run) is None


def test_mfu_of_the_fine_tune():
    read = load_reader("mfu.esft_record")
    run = SimpleNamespace(cell=SimpleNamespace(chips=1),
                          window=SimpleNamespace(steps=48, seconds=50.0),
                          flops_per_step=40e12, peaks={"bf16_flops": 197e12})
    assert read(run) == pytest.approx(100 * 40e12 * 48 / 50 / 197e12)
    run.window.steps = 0
    assert read(run) is None


def test_configuration_keeps_the_catalog_numbers():
    """Every key of the source's config.json is in the file with its
    published value, except the three listed as reduced."""
    c = json.loads((BENCH / "configs" / "moonlight-16b-a3b.json").read_text())
    published = {"num_hidden_layers": 27, "n_routed_experts": 64,
                 "vocab_size": 163840}
    assert set(c["reduced"]) == set(published)
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (9, 8, 20480)
    assert c["n_routed_experts_in_layer"] == published["n_routed_experts"]
    widths = {"hidden_size": 2048, "intermediate_size": 11264,
              "moe_intermediate_size": 1408, "num_attention_heads": 16,
              "q_lora_rank": None, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "num_experts_per_tok": 6,
              "n_shared_experts": 2, "routed_scaling_factor": 2.446,
              "rope_theta": 50000, "max_position_embeddings": 8192}
    assert {k: c[k] for k in widths} == widths
