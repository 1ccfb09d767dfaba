"""End-to-end system behaviour: launcher subprocesses (record -> parallel
replay -> deferred check; crash-restart), greedy generation."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(args, timeout=420):
    env = dict(os.environ, PYTHONPATH=SRC)
    return subprocess.run([sys.executable, "-m"] + args, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_record_replay_launchers_end_to_end(tmp_path):
    run = str(tmp_path / "run")
    r = _run(["repro.launch.train", "--arch", "florbench-100m", "--smoke",
              "--epochs", "3", "--steps-per-epoch", "2", "--batch", "2",
              "--seq", "64", "--run-dir", run, "--no-adaptive"])
    assert r.returncode == 0, r.stderr[-2000:]
    r = _run(["repro.launch.replay", "--run-dir", run, "--arch",
              "florbench-100m", "--smoke", "--epochs", "3",
              "--steps-per-epoch", "2", "--batch", "2", "--seq", "64",
              "--nworkers", "2", "--probe", "train", "--check"])
    assert r.returncode == 0, r.stdout[-500:] + r.stderr[-2000:]
    assert "ok=True" in r.stdout


@pytest.mark.slow
def test_crash_restart_resumes(tmp_path):
    run = str(tmp_path / "run")
    args = ["repro.launch.train", "--arch", "florbench-100m", "--smoke",
            "--epochs", "4", "--steps-per-epoch", "2", "--batch", "2",
            "--seq", "64", "--run-dir", run, "--no-adaptive"]
    r = _run(args)
    assert r.returncode == 0
    r2 = _run(args)
    assert r2.returncode == 0
    assert "resuming" in r2.stdout


@pytest.mark.slow
def test_train_mesh_records_sharded_and_resumes(tmp_path):
    """``--mesh`` in one process: the state lives sharded over the mesh,
    every checkpoint is a v4 sharded manifest, and a relaunch resumes from
    it onto the same mesh."""
    from repro.checkpoint import CheckpointStore
    run = str(tmp_path / "run")
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    args = [sys.executable, "-m", "repro.launch.train", "--arch",
            "florbench-100m", "--smoke", "--steps-per-epoch", "2",
            "--batch", "2", "--seq", "64", "--run-dir", run,
            "--no-adaptive", "--mesh", "2x2"]
    r = subprocess.run(args + ["--epochs", "2"], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "checkpoints: 0,1" in r.stdout
    m = CheckpointStore(os.path.join(run, "store")).resolve_manifest(
        "train@1.0")
    assert m["kind"] == "sharded"
    assert m["mesh"]["axes"] == [["data", 2], ["model", 2]]
    r = subprocess.run(args + ["--epochs", "3"], env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "resuming: restoring epoch 1 checkpoint" in r.stdout
    assert "checkpoints: 0,1,2" in r.stdout


def test_replay_refuses_more_workers_than_the_accelerator_admits(
        monkeypatch, capsys):
    """On an accelerator each worker process holds the whole backend, so
    --nworkers > 1 fails before any work starts and names the cause; CPU
    workers are not limited."""
    from repro.launch import replay
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(replay, "_probe_backend", lambda: ("tpu", 1))
    monkeypatch.setattr(sys, "argv", ["replay", "--run-dir", "unused",
                                      "--nworkers", "2"])
    with pytest.raises(SystemExit) as e:
        replay.main()
    assert e.value.code == 2
    assert "--nworkers 2" in capsys.readouterr().err
    monkeypatch.setattr(replay, "_probe_backend", lambda: ("cpu", 1))
    import argparse
    replay._check_nworkers(argparse.ArgumentParser(), 2)   # no error


def test_compile_cache_dir(monkeypatch):
    """The env var wins untouched; otherwise the cache sits at a fixed
    directory of the checkout."""
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.realpath(os.path.join(SRC, "..", ".jax_cache"))
    assert compile_cache.use_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]


def test_greedy_generate_runs():
    import repro.configs as C
    from repro.data import synthetic_batch
    from repro.models import build_model
    from repro.serve.step import greedy_generate
    cfg = C.get_smoke("granite-3-2b")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    prompt = synthetic_batch(cfg, 2, 16, 0)
    out = greedy_generate(cfg, params, prompt, steps=5, max_len=32)
    assert out.shape == (2, 5)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < cfg.vocab_size).all()


def test_greedy_generate_matches_prefill_argmax():
    """First generated token == argmax of prefill logits (consistency)."""
    import repro.configs as C
    from repro.data import synthetic_batch
    from repro.models import build_model
    cfg = C.get_smoke("florbench-100m").replace(dtype="float32",
                                                param_dtype="float32")
    m = build_model(cfg)
    params = m.init(jax.random.PRNGKey(0))
    prompt = synthetic_batch(cfg, 2, 16, 0)
    caches, logits = jax.jit(lambda p, b: m.prefill(p, b, 32))(params, prompt)
    from repro.serve.step import greedy_generate
    out = greedy_generate(cfg, params, prompt, steps=3, max_len=32)
    np.testing.assert_array_equal(np.asarray(out[:, 0]),
                                  np.asarray(jnp.argmax(logits, -1)))
