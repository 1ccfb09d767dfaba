"""Config registry: published param counts, smoke instantiation, cell skips."""
import pytest

import repro.configs as C

PUBLISHED = {
    # arch: (total params, active params), tolerance 5%
    "granite-3-2b": (2.5e9, 2.5e9),
    "minitron-4b": (4.2e9, 4.2e9),
    "gemma-2b": (2.5e9, 2.5e9),
    "qwen3-14b": (14.8e9, 14.8e9),
    "falcon-mamba-7b": (7.3e9, 7.3e9),
    "deepseek-v3-671b": (671e9, 37e9),
    "mixtral-8x7b": (46.7e9, 12.9e9),
    "seamless-m4t-large-v2": (1.6e9, 1.6e9),
    "llava-next-mistral-7b": (7.2e9, 7.2e9),
    "moonlight-16b-a3b": (16e9, 3e9),
}


def test_registry_complete():
    assert len(C.ARCHS) == 10
    for a in C.ARCHS:
        cfg = C.get(a)
        sm = C.get_smoke(a)
        assert cfg.family == sm.family
        assert sm.param_count() < 5e6, f"{a} smoke too large"


@pytest.mark.parametrize("arch", sorted(PUBLISHED))
def test_param_counts_match_published(arch):
    total, active = PUBLISHED[arch]
    cfg = C.get(arch)
    assert abs(cfg.param_count() - total) / total < 0.06, cfg.param_count()
    assert abs(cfg.active_param_count() - active) / active < 0.06


def test_zamba2_param_count_documented_divergence():
    # assignment specifies a single shared attention block; real Zamba2-7B
    # (two alternating shared blocks + per-invocation LoRA) is ~7.4B. Our
    # config follows the assignment -> ~5.7B (DESIGN.md section 5 note).
    cfg = C.get("zamba2-7b")
    assert 5.0e9 < cfg.param_count() < 6.5e9


def test_shapes_table():
    assert set(C.SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                             "long_500k"}
    assert C.SHAPES["train_4k"].kind == "train"
    assert C.SHAPES["long_500k"].kind == "decode"


def test_long_context_applicability():
    ok, _ = C.cell_applicable("falcon-mamba-7b", "long_500k")
    assert ok
    ok, why = C.cell_applicable("qwen3-14b", "long_500k")
    assert not ok and "full-attention" in why
    # 40-cell accounting: 10 archs x 4 shapes, 7 documented long_500k skips
    cells = [(a, s) for a in C.ARCHS for s in C.SHAPES]
    runnable = [c for c in cells if C.cell_applicable(*c)[0]]
    assert len(cells) == 40
    assert len(runnable) == 33
