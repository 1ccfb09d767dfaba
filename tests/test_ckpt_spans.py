"""Spans and counters inside the checkpoint path: the wait on the writer's
queue, the gather and device-to-host copy, and the writer's job split into
hash, compress and file time — on every record mode, and on the profiler's
clock."""
import glob
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointPipeline, CheckpointStore
from repro.utils.timing import span

COUNTERS = {"queue_wait_s", "copy_s", "hash_s", "compress_s", "file_s"}


@pytest.fixture()
def store(tmp_path):
    return CheckpointStore(str(tmp_path / "store"))


def _state(i: int, n: int = 4096):
    """A small state whose every chunk changes from one checkpoint to the
    next, as in dense training."""
    k = jax.random.PRNGKey(i)
    return {"w": jax.random.normal(k, (n,)),
            "mu": jax.random.normal(jax.random.fold_in(k, 1), (n // 2,))}


def _slow_compress(store, seconds: float):
    real = store._codec.compress

    def compress(data):
        time.sleep(seconds)
        return real(data)
    store._codec.compress = compress


def test_span_adds_its_seconds_into_counters():
    c = {}
    with span("t.a", c, "a_s", ckpt=None):
        time.sleep(0.01)
    with span("t.a", c, "a_s"):
        pass
    with pytest.raises(ValueError):
        with span("t.b", c, "b_s", ckpt="k"):
            raise ValueError
    assert 0.01 <= c["a_s"] < 1.0
    assert c["b_s"] >= 0.0
    with span("t.c"):                  # no counters: nothing to add into
        pass


def test_store_counting_splits_put_chunk(store):
    data = np.random.default_rng(0).bytes(64 * 1024)
    c = {}
    with store.counting(c):
        store.put_chunk(data)
        first = dict(c)
        store.put_chunk(data)          # already there: no compress
    store.put_chunk(bytes(8))          # outside the block: not counted
    assert set(c) == {"hash_s", "compress_s", "file_s", "files_written"}
    assert all(v > 0 for v in first.values())
    assert first["files_written"] == 1   # a pack of its own, at once
    assert c["compress_s"] == first["compress_s"]
    assert c["files_written"] == 1
    assert c["hash_s"] > first["hash_s"] and c["file_s"] > first["file_s"]
    with store.counting(c):
        with store.counting({}):
            pass
        before = c["file_s"]
        store.put_manifest({"key": "m", "leaves": []})
    assert c["file_s"] > before        # the outer block counts again
    assert c["files_written"] == 2


def test_queue_wait_counts_the_blocked_submits(store):
    """With one slot in the queue and a slow writer, the third and fourth
    of four quick blocking submits wait for the writer to finish the job
    two before them."""
    pipe = CheckpointPipeline(store, chunk_words=1024, max_queue=1)
    states = [_state(i) for i in range(6)]
    for i in range(2):                 # compile the fingerprint passes
        pipe.submit(f"k{i}", states[i])
        pipe.drain()
    _slow_compress(store, 0.05)
    for i in range(2, 6):
        pipe.submit(f"k{i}", states[i])
    pipe.drain()
    stats = {s["key"]: s for s in pipe.stats}
    job_s = min(stats[f"k{i}"]["materialize_s"] for i in range(2, 6))
    assert job_s > 0.2                 # 6 chunks at 50 ms each
    for k in ("k4", "k5"):
        assert stats[k]["queue_wait_s"] > 0.5 * job_s
        # the stall stops before the wait
        assert stats[k]["submit_stall_s"] < stats[k]["queue_wait_s"]
    pipe.close()


def test_queue_wait_is_about_zero_with_an_idle_writer(store):
    pipe = CheckpointPipeline(store, chunk_words=1024, max_queue=1)
    for i in range(3):
        pipe.submit(f"k{i}", _state(i))
        pipe.drain()
    for s in pipe.stats:
        assert 0.0 < s["queue_wait_s"] < 0.05
    # a non-blocking submit that finds room returns at once: no wait
    assert pipe.submit("k3", _state(3), block=False) is not None
    pipe.drain()
    assert pipe.stats[-1]["queue_wait_s"] == 0.0
    pipe.close()


def test_queue_wait_lands_on_a_stat_the_writer_already_finished(
        store, monkeypatch):
    """The writer may take a job and finish it before the submitting
    thread has measured its wait: the wait still reaches the stat that
    ``pipeline.stats`` returns."""
    pipe = CheckpointPipeline(store, chunk_words=1024)
    writer = pipe.writer

    def put_after_running(key, fn, block=True):
        writer._run((key, fn))         # the job is done, its stat is out
        time.sleep(0.05)               # and the submitter still waits
        return True
    monkeypatch.setattr(writer, "submit_job", put_after_running)
    pipe.submit("k0", _state(0))
    (stat,) = pipe.stats
    assert stat["queue_wait_s"] >= 0.05
    assert "materialize_s" in stat and stat["hash_s"] > 0.0
    monkeypatch.undo()
    pipe.close()


def test_writer_counters_cover_the_job(store):
    """On a small record at the pipeline's own 64 KiB chunks (96 a
    checkpoint), hash, compress and file time account for the writer's
    job; the gather and copy happen inside the training thread's stall."""
    pipe = CheckpointPipeline(store)
    for i in range(3):
        pipe.submit(f"k{i}", _state(i, n=1024 * 1024))
        pipe.drain()
    assert len(pipe.stats) == 3
    for s in pipe.stats:
        counted = s["hash_s"] + s["compress_s"] + s["file_s"] \
            + s["entropy_s"]
        assert counted <= s["materialize_s"]
        assert counted >= 0.8 * s["materialize_s"], s
        assert 0.0 < s["copy_s"] <= s["submit_stall_s"]
        assert s["hash_s"] > 0.0 and s["compress_s"] > 0.0
    pipe.close()


@pytest.mark.parametrize("mode", ["async", "overlap", "sync"])
def test_every_mode_reports_the_counters(store, mode):
    pipe = CheckpointPipeline(store, chunk_words=1024,
                              async_stage=mode != "sync",
                              overlap=mode == "overlap")
    for i in range(2):
        pipe.submit(f"k{i}", _state(i))
    pipe.drain()
    for s in pipe.stats:
        assert COUNTERS <= set(s)
        assert s["copy_s"] > 0.0 and s["transferred_bytes"] > 0
        assert s["compress_s"] > 0.0
    pipe.close()


def test_sharded_job_reports_the_same_counters(tmp_path):
    """The mesh-aware path (4 virtual CPU devices, a 2x2 mesh) reports the
    flat path's counters, in both of its modes."""
    code = textwrap.dedent(f"""
        import json, os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointPipeline, CheckpointStore
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        sh = NamedSharding(mesh, P("data", "model"))
        out = {{}}
        for overlap in (False, True):
            store = CheckpointStore(os.path.join({str(tmp_path)!r},
                                                 f"store{{overlap}}"))
            pipe = CheckpointPipeline(store, mesh=mesh, chunk_words=64,
                                      overlap=overlap)
            for i in range(2):
                w = jax.device_put(jnp.full((64, 32), float(i)), sh)
                pipe.submit(f"k{{i}}", {{"w": w}})
            pipe.drain()
            out[str(overlap)] = [
                {{k: s.get(k) for k in {sorted(COUNTERS)!r}
                  + ["transferred_bytes", "sharded"]}}
                for s in pipe.stats]
            pipe.close()
        print("COUNTERS " + json.dumps(out))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("COUNTERS ")]
    assert line, res.stderr[-3000:]
    out = json.loads(line[0][len("COUNTERS "):])
    for mode, stats in out.items():
        assert len(stats) == 2, mode
        for s in stats:
            assert s["sharded"] is True
            assert all(isinstance(s[k], float) for k in COUNTERS), (mode, s)
            assert s["transferred_bytes"] > 0 and s["copy_s"] > 0.0
            assert s["compress_s"] > 0.0 and s["file_s"] > 0.0


def _host_lines(trace_dir):
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
              {k: v for k, v in e.stats}) for e in line.events]
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines]


def test_trace_names_the_checkpoint_spans(store, tmp_path):
    """A profiler trace of one submit holds the record path's spans: the
    fingerprint, copy, encode and queue wait on the submitting thread, the
    write on the writer's, all carrying the checkpoint's key."""
    pipe = CheckpointPipeline(store, chunk_words=1024)
    pipe.submit("k0", _state(0))
    pipe.drain()
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("test.submit"):
        pipe.submit("k1", _state(1))
    pipe.drain()
    jax.profiler.stop_trace()
    pipe.close()
    lines = _host_lines(trace_dir)
    main = next(evs for evs in lines
                if any(n == "test.submit" for n, *_ in evs))
    _, lo, hi, _ = next(e for e in main if e[0] == "test.submit")
    names = {n for n, *_ in main}
    assert {"flor.ckpt.fingerprint", "flor.ckpt.copy", "flor.ckpt.encode",
            "flor.ckpt.queue_wait"} <= names
    assert "flor.write" not in names
    wait = [e for e in main if e[0] == "flor.ckpt.queue_wait"]
    assert len(wait) == 1 and lo <= wait[0][1] <= wait[0][2] <= hi
    writes = [e for evs in lines if evs is not main for e in evs
              if e[0] == "flor.write"]
    assert len(writes) == 1
    for n, _, _, stats in main + writes:
        if n.startswith("flor."):
            assert stats.get("ckpt") == "k1", (n, stats)
