"""Delta-aware checkpoint pipeline: record-side fingerprint/transfer flow,
delta-manifest round-trips, full-manifest cadence, GC, crash-safety."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointPipeline, CheckpointStore


@pytest.fixture()
def store(tmp_path):
    return CheckpointStore(str(tmp_path / "store"))


def _tree(step: float):
    """Frozen-majority state: one big frozen leaf, one small hot head."""
    frozen = jax.random.normal(jax.random.PRNGKey(0), (64 * 256,))
    head = jnp.full((256,), step, jnp.float32)
    return {"frozen": frozen, "head": head,
            "opt": {"mu": jnp.full((256,), step / 2, jnp.float32)}}


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if str(x.dtype) != str(y.dtype) or not np.array_equal(x, y):
            return False
    return True


def test_delta_roundtrip_frozen_subtree_bit_identical(store):
    """Record with a frozen majority; every checkpoint (full or delta)
    restores bit-identically, and delta checkpoints transfer only the hot
    fraction."""
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=4,
                              async_stage=False)
    trees = {}
    for i in range(9):
        trees[i] = _tree(float(i + 1))
        s = pipe.submit(f"ck{i}", trees[i], scope="train")
        if s["kind"] == "delta":
            # only head+opt chunks moved: 2 chunks of 1024B out of 66
            assert s["transferred_bytes"] <= 3 * 256 * 4
            assert s["transferred_bytes"] < 0.05 * s["logical_bytes"]
    pipe.close()
    for i in range(9):
        back = store.get_tree(f"ck{i}", like=trees[i])
        assert _leaves_equal(trees[i], back)
        # restored arrays must be writable (np.frombuffer views are not)
        for leaf in jax.tree_util.tree_leaves(back):
            assert np.asarray(leaf).flags.writeable


def test_full_manifest_cadence_bounds_chains(store):
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=3,
                              async_stage=False)
    for i in range(10):
        pipe.submit(f"ck{i}", _tree(float(i)), scope="train")
    pipe.close()
    kinds = [store.get_manifest(f"ck{i}")["kind"] for i in range(10)]
    assert kinds == ["full", "delta", "delta"] * 3 + ["full"]
    # resolve depth never exceeds full_every - 1
    for i in range(10):
        m = store.get_manifest(f"ck{i}")
        depth = 0
        while m.get("parent"):
            m = store.get_manifest(m["parent"])
            depth += 1
        assert depth <= 2


def test_structure_change_forces_full_manifest(store):
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=100,
                              async_stage=False)
    t = _tree(1.0)
    pipe.submit("a", t, scope="s")
    s = pipe.submit("b", dict(t, head=t["head"] + 1), scope="s")
    assert s["kind"] == "delta"
    # dtype change: same bytes-per-chunk topology must NOT alias stale data
    t2 = dict(t, head=(t["head"] + 1).astype(jnp.int32))
    s = pipe.submit("c", t2, scope="s")
    assert s["kind"] == "full"
    # new leaf
    s = pipe.submit("d", dict(t2, extra=jnp.ones((10,))), scope="s")
    assert s["kind"] == "full"
    # leaf removed
    s = pipe.submit("e", t2, scope="s")
    assert s["kind"] == "full"
    pipe.close()
    back = store.get_tree("c", like=t2)
    assert _leaves_equal(t2, back)


def test_delta_restore_matches_full_transfer_restore(store):
    """The acceptance check: a delta-restored tree is bit-identical to a
    full-manifest (put_tree) restore of the same state."""
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=8,
                              async_stage=False)
    t = None
    for i in range(5):
        t = _tree(float(i) * 0.5)
        pipe.submit(f"ck{i}", t, scope="train")
    pipe.close()
    assert store.get_manifest("ck4")["kind"] == "delta"
    store.put_tree("full_ck4", t)                  # classic whole-tree path
    via_delta = store.get_tree("ck4", like=t)
    via_full = store.get_tree("full_ck4", like=t)
    assert _leaves_equal(via_delta, via_full)


def test_mixed_dtypes_roundtrip(store):
    pipe = CheckpointPipeline(store, chunk_words=256, async_stage=False)
    tree = {
        "f32": jax.random.normal(jax.random.PRNGKey(0), (33, 7)),
        "bf16": jax.random.normal(jax.random.PRNGKey(1),
                                  (301,)).astype(jnp.bfloat16),
        "f16": jax.random.normal(jax.random.PRNGKey(2),
                                 (257,)).astype(jnp.float16),
        "i64": jnp.arange(11, dtype=jnp.int64),
        "u8": jnp.asarray(list(range(97)), jnp.uint8),
        "scalar": jnp.asarray(3.5),
        "step": jnp.asarray(7, jnp.int32),
    }
    pipe.submit("a", tree, scope="s")
    bumped = dict(tree, scalar=jnp.asarray(4.5),
                  step=jnp.asarray(8, jnp.int32))
    s = pipe.submit("b", bumped, scope="s")
    pipe.close()
    assert s["kind"] == "delta"
    assert _leaves_equal(bumped, store.get_tree("b", like=bumped))
    assert _leaves_equal(tree, store.get_tree("a", like=tree))


def test_unchanged_resubmission_transfers_nothing(store):
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=100,
                              async_stage=False)
    t = _tree(1.0)
    pipe.submit("a", t, scope="s")
    s = pipe.submit("b", t, scope="s")
    pipe.close()
    assert s["transferred_bytes"] == 0 and s["changed_chunks"] == 0
    assert _leaves_equal(t, store.get_tree("b", like=t))


def test_scopes_are_isolated(store):
    """Interleaved blocks must not diff against each other's trees."""
    pipe = CheckpointPipeline(store, chunk_words=256, async_stage=False)
    ta, tb = _tree(1.0), _tree(100.0)
    pipe.submit("a0", ta, scope="A")
    pipe.submit("b0", tb, scope="B")
    sa = pipe.submit("a1", dict(ta, head=ta["head"] + 1), scope="A")
    sb = pipe.submit("b1", dict(tb, head=tb["head"] + 1), scope="B")
    pipe.close()
    assert sa["kind"] == "delta" and sa["parent"] == "a0"
    assert sb["kind"] == "delta" and sb["parent"] == "b0"
    assert _leaves_equal(dict(ta, head=ta["head"] + 1),
                         store.get_tree("a1", like=ta))
    assert _leaves_equal(dict(tb, head=tb["head"] + 1),
                         store.get_tree("b1", like=tb))


def test_async_pipeline_matches_sync(tmp_path):
    s_async = CheckpointStore(str(tmp_path / "a"))
    s_sync = CheckpointStore(str(tmp_path / "b"))
    pa = CheckpointPipeline(s_async, chunk_words=256, full_every=3)
    ps = CheckpointPipeline(s_sync, chunk_words=256, full_every=3,
                            async_stage=False)
    trees = {i: _tree(float(i)) for i in range(7)}
    for i, t in trees.items():
        pa.submit(f"ck{i}", t, scope="train")
        ps.submit(f"ck{i}", t, scope="train")
    pa.close()
    ps.close()
    assert len(pa.stats) == len(ps.stats) == 7
    for i, t in trees.items():
        assert _leaves_equal(s_async.get_tree(f"ck{i}", like=t),
                             s_sync.get_tree(f"ck{i}", like=t))


def test_gc_keeps_all_live_chunks(store):
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=3,
                              async_stage=False)
    trees = {}
    for i in range(9):
        trees[i] = _tree(float(i))
        pipe.submit(f"ck{i}", trees[i], scope="train")
    pipe.close()
    # retention: keep only the delta ck7 — gc must keep its parent chain
    stats = store.gc(["ck7"])
    assert stats["deleted_manifests"] > 0
    assert store.has("ck7") and store.has("ck6")   # parent closure retained
    back = store.get_tree("ck7", like=trees[7])
    la = jax.tree_util.tree_leaves(trees[7])
    lb = jax.tree_util.tree_leaves(back)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # a second pass with everything live is a no-op
    stats2 = store.gc(store.list_keys())
    assert stats2["deleted_chunks"] == 0 and stats2["deleted_manifests"] == 0


def test_gc_with_real_checkpoint_keys(store):
    """Live keys arrive RAW ('train@2.0') while manifests are stored under
    sanitized names — gc must not treat every real key as dead."""
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=3,
                              async_stage=False)
    trees = {}
    for e in range(5):
        trees[e] = _tree(float(e))
        pipe.submit(f"train@{e}.0", trees[e], scope="train")
    pipe.close()
    stats = store.gc(["train@4.0"])
    assert store.has("train@4.0") and store.has("train@3.0")  # parent chain
    assert stats["deleted_manifests"] == 3
    back = store.get_tree("train@4.0", like=trees[4])
    for x, y in zip(jax.tree_util.tree_leaves(trees[4]),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_python_scalar_leaves_roundtrip(store):
    """State trees may carry plain Python scalars (step counters etc.) —
    the pipeline must checkpoint them like put_tree always did."""
    pipe = CheckpointPipeline(store, chunk_words=256, async_stage=False)
    t = {"w": jnp.ones((1024,)), "step": 3, "lr": 1e-3, "done": False}
    pipe.submit("a", t, scope="s")
    s = pipe.submit("b", dict(t, step=4), scope="s")
    pipe.close()
    assert s["kind"] == "delta"
    back = store.get_tree("b", like=t)
    assert int(back["step"]) == 4
    assert float(back["lr"]) == 1e-3
    assert not bool(back["done"])


def test_gc_collects_orphans(store):
    t = {"x": jnp.arange(4096, dtype=jnp.float32)}
    store.put_tree("keep", t)
    store.put_tree("drop", {"y": jnp.ones((8192,), jnp.float32)})
    before = store.stored_bytes()
    stats = store.gc(["keep"])
    assert stats["deleted_chunks"] >= 1
    assert store.stored_bytes() < before
    assert not store.has("drop")
    back = store.get_tree("keep", like=t)
    np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(t["x"]))


def test_crash_safety_tmp_files_ignored_and_collected(store):
    """Stray .tmp files from a crashed writer are never read as data and are
    not confused with live chunks by gc."""
    t = {"x": jnp.arange(100.0)}
    pipe = CheckpointPipeline(store, chunk_words=256, async_stage=False)
    pipe.submit("good", t, scope="s")
    pipe.close()
    obj_dir = os.path.join(store.root, "objects", "zz")
    os.makedirs(obj_dir, exist_ok=True)
    with open(os.path.join(obj_dir, "deadbeef.zst.tmp.99.1"), "wb") as f:
        f.write(b"garbage")
    with open(os.path.join(store.root, "manifests",
                           "half.msgpack.tmp.99.1"), "wb") as f:
        f.write(b"garbage")
    assert not store.has("half")
    assert "half" not in store.list_keys()
    back = store.get_tree("good", like=t)
    np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(t["x"]))
    store.gc(["good"])          # must not crash on the stray tmp files
    back = store.get_tree("good", like=t)
    np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(t["x"]))


def test_manifest_write_is_atomic_replace(store):
    """put_manifest goes through tmp+os.replace: after the write there is
    exactly one manifest file and no leftover tmp."""
    pipe = CheckpointPipeline(store, chunk_words=256, async_stage=False)
    pipe.submit("k", {"x": jnp.ones((2048,))}, scope="s")
    pipe.close()
    mdir = os.path.join(store.root, "manifests")
    assert sorted(os.listdir(mdir)) == ["k.msgpack"]
    assert not glob.glob(os.path.join(mdir, "*.tmp.*"))


def test_rolling_retention_gc_mid_record(tmp_path):
    """ctx.gc(keep_keys=...) DURING record must keep the active delta-chain
    tip live — otherwise every later checkpoint inherits chunk hashes from
    deleted manifests and is unrestorable."""
    from repro.core.context import FlorContext
    ctx = FlorContext(str(tmp_path / "run"), "record", adaptive=False,
                      async_materialize=False, full_manifest_every=100)
    t = _tree(1.0)
    for e in range(6):
        t = dict(t, head=t["head"] + 1)
        ctx.submit_checkpoint("train", f"train@{e}.0", t, meta={})
    # retention asks to keep only epoch 1; the chain tip train@5.0 (and its
    # parent closure) must survive anyway
    ctx.gc(keep_keys=["train@1.0"])
    assert ctx.store.has("train@1.0") and ctx.store.has("train@5.0")
    # the next delta checkpoint still restores bit-identically
    t = dict(t, head=t["head"] + 1)
    ctx.submit_checkpoint("train", "train@6.0", t, meta={})
    back = ctx.store.get_tree("train@6.0", like=t)
    for x, y in zip(jax.tree_util.tree_leaves(t),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    ctx.finish()


def test_gather_width_bucketing_changes_roundtrip(store):
    """Fluctuating changed-chunk counts (gather width bucketing pads to
    powers of two) must not corrupt what gets stored."""
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=100,
                              async_stage=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (64 * 256,))
    pipe.submit("ck0", {"x": x}, scope="s")
    rng = np.random.default_rng(0)
    for step, nchanged in enumerate([1, 3, 7, 2, 5, 64]):
        x = np.asarray(x).copy()
        rows = rng.choice(64, size=nchanged, replace=False)
        for r in rows:
            x[r * 256] += 1.0
        x = jnp.asarray(x)
        s = pipe.submit(f"ck{step + 1}", {"x": x}, scope="s")
        assert s["changed_chunks"] == nchanged
        back = store.get_tree(f"ck{step + 1}", like={"x": x})
        np.testing.assert_array_equal(np.asarray(back["x"]), np.asarray(x))
    pipe.close()


def test_calibration_leaves_no_artifacts(tmp_path):
    """The adaptive controller's store-throughput probe must not pollute
    list_keys() or stored_bytes() accounting."""
    import repro.flor as flor
    run = str(tmp_path / "run")
    flor.init(run, mode="record", adaptive=True)
    ctx = flor.get_context()
    assert ctx.controller.write_bps >= 1e7         # calibration happened
    assert "__calib__" not in ctx.store.list_keys()
    assert ctx.store.stored_bytes() == 0
    flor.finish()


def test_queue_full_rolls_back_digests(tmp_path):
    """A skipped (queue-full, block=False) checkpoint must not advance the
    device digest state: the next delta still diffs against the last STORED
    checkpoint."""
    store = CheckpointStore(str(tmp_path / "s"))
    pipe = CheckpointPipeline(store, chunk_words=256, full_every=100,
                              async_stage=False)
    t = _tree(1.0)
    pipe.submit("a", t, scope="s")
    # simulate a full queue by swapping in a writer stub that rejects
    class _Full:
        def submit_job(self, key, fn, block=True):
            return False
    real_writer = pipe.writer
    pipe.writer = _Full()
    skipped = pipe.submit("b", dict(t, head=t["head"] + 1), scope="s")
    assert skipped is None
    pipe.writer = real_writer
    # the same state the skipped submit saw: only a rolled-back digest
    # still sees its head as changed
    s = pipe.submit("c", dict(t, head=t["head"] + 1), scope="s")
    pipe.close()
    # head changed relative to "a" — must be transferred even though the
    # intermediate submit saw (and dropped) a newer digest
    assert s["changed_chunks"] >= 1
    back = store.get_tree("c", like=t)
    np.testing.assert_array_equal(np.asarray(back["head"]),
                                  np.asarray(t["head"]) + 1)


def test_tips_name_the_last_stored_key_after_a_refused_submit(tmp_path):
    """A checkpoint refused by a full queue (block=False) does not move its
    scope's tip: tips() and chain_keys() still name the last STORED key of
    each scope."""
    store = CheckpointStore(str(tmp_path / "s"))
    pipe = CheckpointPipeline(store, chunk_words=256, async_stage=False)
    t = _tree(1.0)
    assert pipe.tips() == {}
    pipe.submit("a0", t, scope="a")
    pipe.submit("b0", t, scope="b")
    pipe.submit("a1", dict(t, head=t["head"] + 1), scope="a")

    class _Full:
        def submit_job(self, key, fn, block=True):
            return False
    real_writer, pipe.writer = pipe.writer, _Full()
    assert pipe.submit("a2", dict(t, head=t["head"] + 2), scope="a",
                       block=False) is None
    pipe.writer = real_writer
    assert pipe.tips() == {"a": "a1", "b": "b0"}
    assert sorted(pipe.chain_keys()) == ["a1", "b0"]
    assert all(store.has(k) for k in pipe.tips().values())
    pipe.close()
