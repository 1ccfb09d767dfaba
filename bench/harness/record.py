"""The record window: a ``flor.Session`` in record mode with its checkpoint
scope around the epoch loop, one checkpoint at the end of each inner loop of
``steps_per_ckpt`` steps. One inner loop and its checkpoint is an interval.

Warm-up opens with an interval of the ``CHECK_STEPS`` steps the reference
follows, whose close submits the first (full) checkpoint; a mix that sets
``await_full`` then waits until that checkpoint is durable, so that no delta
queues behind it. Whole intervals follow until the record pipeline is in its
steady state: the full checkpoint is durable, a delta has been submitted,
and the last submit either blocked on the writer's full queue (the writer
sets the pace) or found the writer idle (the step sets it). The window then
holds the whole number of intervals nearest to ``seconds``, judged by the
length of its first interval, so every run holds the same mix of work, and
rates are taken over all of the window's steps and time.

Once the window has closed, one of its checkpoints (chosen by the seed) is
read back from the store as soon as it is durable, while the writer finishes
the others.
"""
from __future__ import annotations

import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from harness import data
from harness.model import seed_key
from harness.trace import WINDOW_SPAN

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_STEPS = 3           # the steps the reference follows
MAX_WINDOW = 1000         # intervals; the epoch loop needs a finite range
DURABLE_WAIT_S = 300      # longest wait for the checked checkpoint's writer


class CompileCounter:
    """Counts programs compiled (or read from the persistent cache) in this
    process, so a window can show that none were."""

    def __init__(self):
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration


@dataclass
class Window:
    seconds: float = 0.0
    steps: int = 0
    tokens: int = 0
    keys: list = field(default_factory=list)      # checkpoints in the window
    compiles: int = 0
    steady: bool = True
    warmup_intervals: int = 0
    last_warmup_key: str = ""


@dataclass
class Captured:
    """What the run's first steps produced, for the reference to follow:
    the initial trainable params, the first-step Adam mean, the trainable
    params after ``CHECK_STEPS`` steps and each step's loss."""
    p0: object = None
    mu1: object = None
    p3: object = None
    losses: list = field(default_factory=list)
    batches: list = field(default_factory=list)


def _host(tree):
    return jax.tree_util.tree_map(np.asarray, jax.device_get(tree))


class Recorder:
    def __init__(self, system, traffic: dict, seed: int, run_dir: Path,
                 compiles: CompileCounter, fault=None):
        self.system = system
        self.traffic = traffic
        self.seed = seed
        self.run_dir = run_dir
        self.compiles = compiles
        self.fault = fault or {}
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq"])
        self.k = int(traffic["steps_per_ckpt"])
        self.max_warmup = int(traffic["max_warmup_intervals"])
        self.await_full = bool(traffic.get("await_full", False))
        self.vocab = system.cfg.vocab_size
        self.captured = Captured()
        self.window = Window()
        self.stats = []            # the pipeline's per-checkpoint stats
        self.store_root = None
        self.check_key = None      # the window checkpoint compared after
        self.check_state = None    # what was submitted there, on the host
        self.restored = None       # and what the store gives back

    def feed(self, g: int):
        toks = data.tokens(g, self.seed, self.batch, self.seq, self.vocab)
        if g < CHECK_STEPS:
            self.captured.batches.append(toks)
        return {"tokens": jnp.asarray(toks)}

    def _capture(self, g: int, state, metrics):
        c = self.captured
        if g <= CHECK_STEPS:
            c.losses.append(metrics["loss"])
        if g == 1:
            c.mu1 = _host(state["train"].mu)
        if g == CHECK_STEPS:
            c.p3 = _host(state["train"].params)

    def run(self, seconds: float, on_window_open=None, on_window_close=None,
            setup_done=None) -> "Recorder":
        import repro.flor as flor
        system = self.system
        shutil.rmtree(self.run_dir, ignore_errors=True)
        state = system.init_state(seed_key(self.seed))
        self.captured.p0 = _host(state["train"].params)
        step = self.fault["step"](system) if "step" in self.fault \
            else system.step
        w = self.window
        spec = flor.RecordSpec(adaptive=False)
        with flor.Session(str(self.run_dir), mode="record",
                          record=spec) as sess:
            pipeline = sess.ctx.pipeline
            self.store_root = sess.store_root
            with sess.checkpointing(state=state) as ckpt:
                del state
                g, closed, min_delta_close = 0, 0, None
                opened, t_w0, span, intervals = False, None, None, 1
                for epoch in sess.loop("epochs", range(self.max_warmup
                                                             + MAX_WINDOW)):
                    n = CHECK_STEPS if epoch == 0 else self.k
                    inner = iter(sess.loop("train", range(n)))
                    for s in range(n):
                        next(inner)
                        with jax.profiler.TraceAnnotation("bench.feed"):
                            batch = self.feed(g)
                        with jax.profiler.TraceAnnotation("bench.step"):
                            ckpt.state, m = step(ckpt.state, batch)
                        g += 1
                        self._capture(g, ckpt.state, m)
                    jax.block_until_ready(ckpt.state)
                    done_before = len(pipeline.stats)
                    t_ready = time.perf_counter()
                    with jax.profiler.TraceAnnotation("bench.checkpoint"):
                        for _ in inner:       # closing the loop checkpoints
                            raise RuntimeError("train loop ran past its range")
                    t1 = time.perf_counter()
                    close_s = t1 - t_ready
                    closed += 1
                    key = f"train@{epoch}.0"
                    if opened:
                        w.steps += self.k
                        w.keys.append(key)
                        if len(w.keys) == 1 + self.seed % 2 \
                                or self.check_key is None:
                            self.check_key, self.check_state = key, ckpt.state
                        if len(w.keys) == 1:
                            intervals = max(1, round(seconds / (t1 - t_w0)))
                        if len(w.keys) == intervals:
                            w.seconds = t1 - t_w0
                            w.compiles = self.compiles.n - w.compiles
                            span.__exit__(None, None, None)
                            if on_window_close:
                                on_window_close()
                            break
                        continue
                    # ---- warm-up: is the pipeline in its steady state?
                    if closed == 1 and self.await_full:
                        pipeline.drain()
                    blocked = (min_delta_close is not None and close_s
                               > 1.5 * min_delta_close + 0.5)
                    if closed >= 2:
                        min_delta_close = close_s if min_delta_close is None \
                            else min(min_delta_close, close_s)
                    idle = done_before == closed - 1
                    durable = len(pipeline.stats) >= 1
                    steady = closed >= 2 and durable and (blocked or idle)
                    if steady or closed >= self.max_warmup:
                        w.steady = steady
                        w.warmup_intervals = closed
                        w.last_warmup_key = key
                        if setup_done:
                            setup_done()
                        if on_window_open:
                            on_window_open()
                        span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
                        span.__enter__()
                        w.compiles = self.compiles.n
                        opened = True
                        t_w0 = time.perf_counter()
                self.memory_peak = _memory_peak()
                self.final_loss = float(m["loss"])
                t_closed = time.perf_counter()
                self.check_state = _host({"state": self.check_state})
                _await_durable(pipeline, self.check_key)
                from repro.checkpoint.store import CheckpointStore
                t0 = time.perf_counter()
                self.restored = CheckpointStore(self.store_root).get_tree(
                    self.check_key)
                self.restore_s = time.perf_counter() - t0
        self.drain_s = time.perf_counter() - t_closed
        w.tokens = w.steps * self.batch * self.seq
        self.stats = [s for s in pipeline.stats if s["key"] in w.keys]
        self.captured.losses = [float(x) for x in self.captured.losses]
        return self


def _await_durable(pipeline, key: str):
    """Wait until the writer has made ``key`` durable."""
    t0 = time.monotonic()
    while not any(s["key"] == key for s in pipeline.stats):
        if time.monotonic() - t0 > DURABLE_WAIT_S:
            raise RuntimeError(f"checkpoint {key} not durable after "
                               f"{DURABLE_WAIT_S} s")
        time.sleep(0.05)


def _memory_peak():
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
