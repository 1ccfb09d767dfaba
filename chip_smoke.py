#!/usr/bin/env python3
"""Smoke test of the record -> resume -> replay path on a TPU, at the full
width of florbench-100m (12 layers, d_model 768, vocab 32768; random
weights from a fixed seed).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded-record path

One chip runs five phases through the entry points a user calls:

  kernels      the four record-path Pallas kernels on real leaves: digests
               bit-identical to the jnp references, the fused changed mask
               flags exactly the perturbed rows, the q8/q4 gathers match
               their references, and each op lowers to a Mosaic kernel
               (``tpu_custom_call``) rather than a reference path;
  record       ``repro.launch.train`` for 3 epochs, one checkpoint each;
  resume       the same command with ``--epochs 4`` restores epoch 2;
  error-bound  a ``flor.Session`` record with ``ckpt_error_bounds`` on the
               Adam moments (the q4/q8 gathers), restored within the bound;
  replay       ``repro.launch.replay --probe train --check``.

``--chips 4`` runs only the multi-chip path: a ``flor.Session`` record of
the same model sharded over a 2x2 mesh in one process, then restores onto a
4x1 mesh and onto one chip, each compared bit for bit with the recorded
state.

Each phase is a child process, one at a time: a process that opens the TPU
holds it until it exits, so this parent never imports JAX. A phase that
finds no TPU fails, and so does the script; there is no CPU fallback.
Phase walls and compile times are printed as they finish; the last line of
stdout is ``{"ok": true, "device": {"platform", "kind", "count"}}``. Run
dirs and per-phase logs go under ``--work-dir`` (default
``<checkout>/.smoke_runs``), which is emptied first.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
ARCH = "florbench-100m"
BATCH, SEQ, STEPS = 8, 512, 4
PHASE_TIMEOUT_S = 900
# one-chip record: the launcher's own flags, at full width
TRAIN_ARGS = ["--arch", ARCH, "--steps-per-epoch", str(STEPS),
              "--batch", str(BATCH), "--seq", str(SEQ)]


# ------------------------------------------------------------ child side --
def _require_tpu(chips: int):
    """The devices JAX found, or exit non-zero when they are not TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"chip_smoke: needs {chips} TPU chip(s); JAX found "
                 f"{len(devs)} {devs[0].platform} device(s)")
    print("DEVICE " + json.dumps({"platform": devs[0].platform,
                                  "kind": devs[0].device_kind,
                                  "count": len(devs)}), flush=True)
    return devs


def _check(ok: bool, what: str):
    if not ok:
        sys.exit(f"chip_smoke: {what}")


def _config():
    import repro.configs as C
    return C.get(ARCH)


def _state_paths(tree) -> dict:
    """{keystr path: host array} of a pytree."""
    import jax
    import numpy as np
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in flat}


def _nibbles_close(a, b) -> bool:
    """Packed int4 rows agree to one quantization level per element."""
    import numpy as np
    for sa, sb in ((a & 0xF, b & 0xF), (a >> 4, b >> 4)):
        da = sa.astype(np.int8) - ((sa > 7) << 4)
        db = sb.astype(np.int8) - ((sb > 7) << 4)
        if np.max(np.abs(da.astype(np.int32) - db)) > 1:
            return False
    return True


def phase_kernels(args):
    _require_tpu(1)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.pipeline import PIPELINE_CHUNK_WORDS
    from repro.kernels import ops, ref
    from repro.kernels.quantize import Q4_BLOCK, Q8_BLOCK

    cfg = _config()
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    leaves = {
        "embed_f32": 0.02 * jax.random.normal(
            k1, (cfg.vocab_size, cfg.d_model), jnp.float32),
        "mlp_bf16": (0.02 * jax.random.normal(
            k2, (cfg.d_model, cfg.d_ff), jnp.float32)).astype(jnp.bfloat16),
    }
    ref_fp = jax.jit(ref.fingerprint_ref)
    for name, leaf in leaves.items():
        nrow, ncol = leaf.shape
        rows = np.array([3, nrow // 2, nrow - 1])
        leaf2 = leaf.at[rows].add(jnp.asarray(1.0, leaf.dtype))
        for cw in (ops.CHUNK_WORDS, PIPELINE_CHUNK_WORDS):
            t0 = time.perf_counter()
            blocks = ops._as_u32_blocks(leaf, cw)
            g = blocks.shape[0]
            d = ops.fingerprint_leaf(leaf, cw)
            _check(np.array_equal(np.asarray(d), np.asarray(ref_fp(blocks))),
                   f"{name} cw={cw}: Pallas digests differ from "
                   f"fingerprint_ref")
            # one element per u32 word for f32 and bf16 alike
            want = sorted({c for r in rows for c in range(
                r * ncol // cw, ((r + 1) * ncol - 1) // cw + 1)})
            d2, mask = ops.fingerprint_and_changed(leaf2, d, cw)
            _check(np.array_equal(
                np.asarray(d2),
                np.asarray(ref_fp(ops._as_u32_blocks(leaf2, cw)))),
                f"{name} cw={cw}: fused digests differ from fingerprint_ref")
            got = np.flatnonzero(np.asarray(mask)).tolist()
            _check(got == want, f"{name} cw={cw}: mask {got} != rows {want}")
            fblocks = ops._padded_float_blocks(leaf, cw)
            picks = (np.random.default_rng(cw).permutation(g)[:64],
                     np.arange(g))
            for idx in picks:
                label = f"C={len(idx)}"
                idx = jnp.asarray(idx, jnp.int32)
                q, s = ops.gather_quantize_blocks(leaf, idx, cw)
                qr, sr = ref.gather_quantize_ref(fblocks, idx,
                                                 min(Q8_BLOCK, cw))
                _check(np.allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
                       and np.max(np.abs(np.asarray(q, np.int32)
                                         - np.asarray(qr, np.int32))) <= 1,
                       f"{name} cw={cw} {label}: q8 differs from "
                       f"gather_quantize_ref")
                p, s = ops.gather_quantize4_blocks(leaf, idx, cw)
                pr, sr = ref.gather_quantize4_ref(fblocks, idx,
                                                  min(Q4_BLOCK, cw))
                _check(np.allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
                       and _nibbles_close(np.asarray(p), np.asarray(pr)),
                       f"{name} cw={cw} {label}: q4 differs from "
                       f"gather_quantize4_ref")
            idx = jnp.asarray(want, jnp.int32)
            lowered = {
                "fingerprint_leaf": ops.fingerprint_leaf.lower(
                    leaf, chunk_words=cw),
                "fingerprint_and_changed": ops.fingerprint_and_changed.lower(
                    leaf2, d, chunk_words=cw),
                "gather_quantize_blocks": ops.gather_quantize_blocks.lower(
                    leaf, idx, chunk_words=cw),
                "gather_quantize4_blocks": ops.gather_quantize4_blocks.lower(
                    leaf, idx, chunk_words=cw),
            }
            for op, low in lowered.items():
                _check("tpu_custom_call" in low.as_text(),
                       f"{op} did not lower to a Mosaic kernel")
            print(f"kernels {name} {leaf.shape} cw={cw} G={g}: digests "
                  f"bit-identical, mask rows {got}, q8/q4 gathers within "
                  f"tolerance at C={min(64, g)} and C={g}, 4 ops lower to "
                  f"tpu_custom_call ({time.perf_counter() - t0:.3f}s)",
                  flush=True)


def phase_error_bound(args):
    _require_tpu(1)
    import jax
    import numpy as np

    import repro.flor as flor
    from repro.data import synthetic_batch
    from repro.launch.compile_cache import use_compile_cache
    from repro.train.step import build_train_step

    use_compile_cache()
    cfg = _config()
    init_state, train_step = build_train_step(cfg)
    ts = jax.jit(train_step)
    atol = 1e-4
    with flor.Session(args.run_dir, record=flor.RecordSpec(
            adaptive=False,
            ckpt_error_bounds={"mu": atol, "nu": atol})) as sess:
        with sess.checkpointing(
                state=jax.jit(init_state)(jax.random.PRNGKey(0))) as ckpt:
            for epoch in sess.loop("epochs", range(2)):
                for s in sess.loop("train", range(2)):
                    ckpt.state, _ = ts(ckpt.state, synthetic_batch(
                        cfg, BATCH, SEQ, epoch * 2 + s))
        state = ckpt.state
        store = sess.ctx.store
    key = "train@1.0"
    encs: dict = {}
    for leaf in store.resolve_manifest(key)["leaves"]:
        for e in leaf.get("enc") or ["raw"] * len(leaf["chunks"]):
            encs[e.split("+")[0]] = encs.get(e.split("+")[0], 0) + 1
    _check(encs.get("q4", 0) + encs.get("q8", 0) > 0,
           f"no chunk took a lossy encoding: {encs}")
    truth = _state_paths(state)
    got = _state_paths(store.get_tree(key, like=jax.device_get(state)))
    worst = 0.0
    for p, want in truth.items():
        if ".mu" in p or ".nu" in p:
            err = float(np.max(np.abs(got[p].astype(np.float64)
                                      - want.astype(np.float64))))
            _check(err <= atol, f"{p}: restore error {err} > {atol}")
            worst = max(worst, err)
        else:
            _check(np.array_equal(got[p], want), f"{p}: exact slot changed")
    print(f"error-bound: chunk encodings {dict(sorted(encs.items()))}, "
          f"max moment error {worst:.3g} <= {atol:g}, other leaves "
          f"bit-identical", flush=True)


def phase_sharded(args):
    _require_tpu(4)
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.flor as flor
    from repro.checkpoint import restore_sharded_tree
    from repro.data import synthetic_batch
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import state_shardings
    from repro.parallel import use_mesh
    from repro.train.step import build_train_step

    use_compile_cache()
    cfg = _config()
    init_state, train_step = build_train_step(cfg)
    axes = ("data", "model")
    mesh = make_mesh((2, 2), axes)
    rng = jax.random.PRNGKey(0)
    t0 = time.perf_counter()
    with use_mesh(mesh):
        st_sh = state_shardings(cfg, mesh, jax.eval_shape(init_state, rng))
        ts = jax.jit(train_step,
                     out_shardings=(st_sh, NamedSharding(mesh, P())))
        with flor.Session(args.run_dir, record=flor.RecordSpec(
                mesh=mesh, adaptive=False)) as sess:
            with sess.checkpointing(state=jax.jit(
                    init_state, out_shardings=st_sh)(rng)) as ckpt:
                for epoch in sess.loop("epochs", range(2)):
                    for s in sess.loop("train", range(2)):
                        ckpt.state, m = ts(ckpt.state, synthetic_batch(
                            cfg, BATCH, SEQ, epoch * 2 + s))
                    loss = float(m["loss"])
                    _check(math.isfinite(loss), f"epoch {epoch} loss {loss}")
                    print(f"sharded epoch {epoch} loss {loss:.6f}",
                          flush=True)
            state = ckpt.state
            store = sess.ctx.store
    print(f"sharded record (2x2): {time.perf_counter() - t0:.3f}s",
          flush=True)
    truth = _state_paths({"state": state})
    targets = {"4x1": make_mesh((4, 1), axes),
               "1 chip": make_mesh((1, 1), axes, devices=jax.devices()[:1])}
    for label, target in targets.items():
        t0 = time.perf_counter()
        out = restore_sharded_tree(store, "train@1.0", target)
        _check(set(out) == set(truth),
               f"restored paths differ: {sorted(set(out) ^ set(truth))[:5]}")
        bad = [p for p, v in out.items()
               if not np.array_equal(np.asarray(jax.device_get(v)), truth[p])]
        _check(not bad, f"restore onto {label} differs at {bad[:5]}")
        print(f"restore onto {label}: {len(out)} leaves bit-identical "
              f"({time.perf_counter() - t0:.3f}s)", flush=True)


PHASES = {"kernels": phase_kernels, "error-bound": phase_error_bound,
          "sharded": phase_sharded}


# ----------------------------------------------------------- parent side --
class Runner:
    """Runs each phase as a child in its own process group and keeps its
    full output in ``<work>/logs/<phase>.log``."""

    def __init__(self, work: Path):
        self.logs = work / "logs"
        self.logs.mkdir(parents=True)
        self.env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            [str(SRC)] + [p for p in os.environ.get("PYTHONPATH", "")
                          .split(os.pathsep) if p]))
        self.device = None

    def run(self, name: str, cmd: list) -> str:
        t0 = time.perf_counter()
        proc = subprocess.Popen(cmd, cwd=ROOT, env=self.env, text=True,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            out, _ = proc.communicate()
        finally:
            # reap anything the phase left behind (replay workers)
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        wall = time.perf_counter() - t0
        (self.logs / f"{name}.log").write_text(out)
        if proc.returncode != 0:
            sys.stderr.write(out[-4000:])
            sys.exit(f"chip_smoke: phase {name} failed (rc {proc.returncode}"
                     f" after {wall:.3f}s); log {self.logs / name}.log")
        for line in out.splitlines():
            if line.startswith("DEVICE "):
                self.device = json.loads(line[len("DEVICE "):])
            elif line.startswith(("kernels ", "error-bound:", "sharded ",
                                  "restore onto ")):
                print(f"  {line}", flush=True)
        print(f"phase {name}: ok, wall {wall:.3f}s", flush=True)
        return out


def _find(pattern: str, out: str, phase: str) -> list:
    found = re.findall(pattern, out, re.MULTILINE)
    if not found:
        sys.exit(f"chip_smoke: phase {phase} printed no {pattern!r}")
    return found


def _check_train(out: str, phase: str, epochs: int) -> float:
    """Finite per-epoch losses, one checkpoint per epoch; returns the
    process's compile seconds."""
    losses = {int(e): float(v) for e, v in
              _find(r"^epoch (\d+) done loss (\S+)$", out, phase)}
    bad = {e: v for e, v in losses.items() if not math.isfinite(v)}
    if bad:
        sys.exit(f"chip_smoke: phase {phase}: non-finite loss {bad}")
    ckpts = _find(r"^checkpoints: (\S*)$", out, phase)[-1]
    if ckpts != ",".join(str(e) for e in range(epochs)):
        sys.exit(f"chip_smoke: phase {phase}: checkpoints {ckpts!r}, want "
                 f"one per epoch 0..{epochs - 1}")
    secs, n = _find(r"^xla compile (\S+)s over (\d+) programs$", out,
                    phase)[-1]
    print(f"  {phase}: losses {losses}, checkpoints {ckpts}, compile "
          f"{secs}s over {n} programs", flush=True)
    return float(secs)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-record path on a 2x2 mesh")
    ap.add_argument("--work-dir", default=str(ROOT / ".smoke_runs"),
                    help="run dirs and phase logs (emptied first)")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)
    ap.add_argument("--run-dir", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase:                      # child: one phase in this process
        sys.path.insert(0, str(SRC))
        PHASES[args.phase](args)
        return
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {SRC}")
    work = Path(args.work_dir).resolve()
    shutil.rmtree(work, ignore_errors=True)
    runner = Runner(work)
    me = [sys.executable, str(Path(__file__).resolve())]

    if args.chips == 4:
        runner.run("sharded", me + ["--phase", "sharded",
                                    "--run-dir", str(work / "sharded")])
    else:
        run = str(work / "main")
        train = [sys.executable, "-m", "repro.launch.train", *TRAIN_ARGS,
                 "--run-dir", run, "--no-adaptive"]
        runner.run("kernels", me + ["--phase", "kernels"])
        cold = _check_train(runner.run("record", train + ["--epochs", "3"]),
                            "record", 3)
        out = runner.run("resume", train + ["--epochs", "4"])
        _find(r"^resuming: restoring epoch 2 checkpoint$", out, "resume")
        warm = _check_train(out, "resume", 4)
        print(f"compile: cold {cold:.3f}s (record), warm {warm:.3f}s "
              f"(resume, persistent cache)", flush=True)
        runner.run("error-bound", me + ["--phase", "error-bound",
                                        "--run-dir", str(work / "eb")])
        out = runner.run("replay", [
            sys.executable, "-m", "repro.launch.replay", *TRAIN_ARGS,
            "--run-dir", run, "--epochs", "4", "--probe", "train",
            "--nworkers", "1", "--check"])
        _find(r"^deferred check: ok=True", out, "replay")
    if runner.device is None:
        sys.exit("chip_smoke: no phase reported its device")
    print(json.dumps({"ok": True, "device": runner.device}), flush=True)


if __name__ == "__main__":
    main()
