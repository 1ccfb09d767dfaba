"""Cross-family record -> hindsight-replay matrix: every model family the
paper's benchmark sweeps (dense, MoE, SSM, hybrid/MLA, audio enc-dec, VLM)
must record through the full Session path and hindsight-replay to
BIT-IDENTICAL state and log rows — replay correctness is a property of the
substrate, not of one architecture's numerics."""
import jax
import numpy as np
import pytest

import repro.configs as C
import repro.flor as flor
from repro.data import synthetic_batch
from repro.train.step import build_train_step

EPOCHS, STEPS = 2, 2
BATCH, SEQ = 2, 32

# one representative arch per family
FAMILIES = [
    ("dense", "gemma-2b"),
    ("moe", "mixtral-8x7b"),
    ("ssm", "falcon-mamba-7b"),
    ("hybrid", "zamba2-7b"),
    ("audio", "seamless-m4t-large-v2"),
    ("vlm", "llava-next-mistral-7b"),
    ("moe", "moonlight-16b-a3b"),
]
# the second MoE is one chip's share of an expert-parallel layer
IDS = {"moonlight-16b-a3b": "moe-share"}


def _loop(sess, cfg, init_state, ts, probe=False):
    state = jax.jit(init_state)(jax.random.PRNGKey(0))
    with sess.checkpointing(state=state) as ckpt:
        for epoch in sess.loop("epochs", range(EPOCHS)):
            for s in sess.loop("train", range(STEPS)):
                b = synthetic_batch(cfg, BATCH, SEQ, epoch * STEPS + s)
                ckpt.state, m = ts(ckpt.state, b)
                if probe:
                    flor.log("probe_gnorm", m["grad_norm"])
            if sess.executed("train"):
                flor.log("loss", m["loss"])
        return ckpt.state


def _leaves_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


@pytest.mark.slow
@pytest.mark.parametrize("family,arch", FAMILIES,
                         ids=[IDS.get(a, f) for f, a in FAMILIES])
def test_family_record_replay_bit_identical(tmp_path, family, arch):
    cfg = C.get_smoke(arch)
    assert cfg.family == family
    init_state, train_step = build_train_step(cfg)
    ts = jax.jit(train_step)
    run = str(tmp_path / arch)

    with flor.Session(run, mode="record",
                      record=flor.RecordSpec(adaptive=False)) as sess:
        final = _loop(sess, cfg, init_state, ts)

    with flor.Session(run, mode="replay",
                      replay=flor.ReplaySpec(probed={"train"})) as sess:
        out = _loop(sess, cfg, init_state, ts, probe=True)

    # 1) replayed final state is bit-identical
    assert _leaves_equal(final, out), f"{arch}: state diverged in replay"
    # 2) every recorded log row is reproduced bit-identically, and the
    #    hindsight probes landed
    rec, reps = flor.run_logs(run)
    res = flor.deferred_check(rec, reps)
    assert res.ok, (arch, res.anomalies)
    assert res.compared == EPOCHS            # one loss row per epoch
    assert res.hindsight_only == EPOCHS * STEPS
    from repro.logging import FingerprintLog
    rec_loss = [r["value"] for r in FingerprintLog.read(rec)
                if r["key"] == "loss"]
    rep_loss = [r["value"] for p in reps for r in FingerprintLog.read(p)
                if r["key"] == "loss"]
    assert rec_loss == rep_loss and len(rec_loss) == EPOCHS
