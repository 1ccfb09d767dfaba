"""Pack files in the checkpoint store: a writer job's chunks go into a few
append-only packs, published by rename, found by fresh handles and other
processes, deduplicated, compacted by gc, and counted byte for byte."""
import glob
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointPipeline, CheckpointStore
from repro.checkpoint import packs
from repro.checkpoint.store import _atomic_write, _hash


@pytest.fixture()
def store(tmp_path):
    return CheckpointStore(str(tmp_path / "store"))


def _chunks(n, seed=0, size=4096):
    rng = np.random.default_rng(seed)
    return [rng.bytes(size) for _ in range(n)]


def _pack_files(root):
    return sorted(glob.glob(os.path.join(root, "**", "*" + packs.SUFFIX),
                            recursive=True))


def _v1(key, hashes):
    return {"key": key, "treedef": "", "meta": {},
            "leaves": [{"path": "x", "dtype": "uint8",
                        "shape": [4096 * len(hashes)], "chunks": hashes}]}


def test_pack_roundtrip_and_trailer(tmp_path):
    d = str(tmp_path)
    w = packs.PackWriter(d)
    payloads = {f"{i:032x}": bytes([i]) * (100 + i) for i in range(5)}
    costs = [w.add(h, p) for h, p in payloads.items()]
    size = w.publish()
    assert os.path.getsize(w.path) == size == sum(costs)
    assert costs[0] == 100 + packs.ENTRY.size + packs.TRAILER.size
    idx = packs.read_index(w.path)
    assert list(idx) == list(payloads)
    for h, (off, n) in idx.items():
        assert packs.read_record(w.path, off, n) == payloads[h]
    assert glob.glob(os.path.join(d, "*.tmp.*")) == []


def test_not_a_pack_is_refused(tmp_path):
    p = str(tmp_path / ("x" + packs.SUFFIX))
    with open(p, "wb") as f:
        f.write(b"garbage" * 10)
    with pytest.raises(ValueError):
        packs.read_index(p)


def test_killed_writer_tmp_pack_is_never_read_and_gc_reclaims_it(
        store, tmp_path):
    """A writer killed inside ``packing()`` leaves a tmp pack: no handle
    reads its chunks, and gc reclaims it once it is stale."""
    code = textwrap.dedent(f"""
        import os
        from repro.checkpoint import CheckpointStore
        store = CheckpointStore({store.root!r})
        with store.packing():
            store.put_chunk(b"a" * 5000)
            store.put_chunk(b"b" * 5000)
            os._exit(0)                 # killed before publication
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    (tmp,) = glob.glob(os.path.join(store.root, "objects", "packs",
                                    "*.tmp.*"))
    assert _pack_files(store.root) == []
    with pytest.raises(FileNotFoundError):
        store.get_chunk(_hash(b"a" * 5000))
    assert store.stats()["chunks"] == 0
    # the killed writer's chunk is written anew, not taken from the tmp
    h, nb, new = store.put_chunk(b"a" * 5000)
    assert new and nb > 0
    store.put_manifest(_v1("k", [h]))
    assert store.gc(["k"])["deleted_tmp_files"] == 0    # fresh: left alone
    past = os.path.getmtime(tmp) - 3600
    os.utime(tmp, (past, past))
    stats = store.gc(["k"])
    assert stats["deleted_tmp_files"] == 1 and not os.path.exists(tmp)
    assert store.get_chunk(h) == b"a" * 5000


def test_dedup_within_an_open_pack_and_across_packs(store):
    a, b = _chunks(2)
    with store.packing():
        ha, nb1, new1 = store.put_chunk(a)
        _, nb2, new2 = store.put_chunk(a)        # pending in the open pack
        hb, _, _ = store.put_chunk(b)
    assert new1 and nb1 > 0 and (nb2, new2) == (0, False)
    assert len(_pack_files(store.root)) == 1
    with store.packing():
        assert store.put_chunk(a)[1:] == (0, False)   # in a published pack
    assert CheckpointStore(store.root).put_chunk(b)[1:] == (0, False)
    assert len(_pack_files(store.root)) == 1
    assert store.stats()["chunks"] == 2


def test_fresh_handle_and_other_process_find_published_chunks(store):
    reader = CheckpointStore(store.root)
    first = _chunks(3, seed=1)
    with store.packing():
        hashes = [store.put_chunk(c)[0] for c in first]
    # a handle that indexed the pool before the pack was published
    assert reader.get_chunk(hashes[0]) == first[0]
    later = _chunks(2, seed=2)
    with store.packing():
        hashes += [store.put_chunk(c)[0] for c in later]
    assert reader.get_chunk(hashes[-1]) == later[-1]   # rescans on a miss
    assert CheckpointStore(store.root).get_chunk(hashes[1]) == first[1]
    code = textwrap.dedent(f"""
        import hashlib, json
        from repro.checkpoint import CheckpointStore
        s = CheckpointStore({store.root!r})
        print(json.dumps([hashlib.sha256(s.get_chunk(h)).hexdigest()
                          for h in {hashes!r}]))
    """)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300)
    import hashlib
    want = [hashlib.sha256(c).hexdigest() for c in first + later]
    assert json.loads(res.stdout.splitlines()[-1]) == want, res.stderr


def test_manifest_inside_packing_names_only_published_chunks(store):
    data = _chunks(4, seed=3)
    other = CheckpointStore(store.root)
    with store.packing():
        hashes = [store.put_chunk(c)[0] for c in data]
        assert _pack_files(store.root) == []          # still open
        store.put_manifest(_v1("m", hashes))
        assert len(_pack_files(store.root)) == 1      # published first
        for h, c in zip(hashes, data):
            assert other.get_chunk(h) == c
        h_after = store.put_chunk(b"after" * 100)[0]  # a new pack opens
    assert other.get_chunk(h_after) == b"after" * 100
    assert len(_pack_files(store.root)) == 2
    assert glob.glob(os.path.join(store.root, "**", "*.tmp.*"),
                     recursive=True) == []


def test_pack_rolls_over_at_its_size(store, monkeypatch):
    monkeypatch.setattr(packs, "PACK_BYTES", 10_000)
    with store.packing():
        hashes = [store.put_chunk(c)[0] for c in _chunks(7, seed=4)]
        assert len(_pack_files(store.root)) == 2      # 3 records each
    assert len(_pack_files(store.root)) == 3
    fresh = CheckpointStore(store.root)
    assert all(fresh.get_chunk(h) for h in hashes)


def test_gc_compacts_a_partly_live_pack(store):
    data = _chunks(6, seed=5)
    with store.packing():
        hashes = [store.put_chunk(c)[0] for c in data]
        store.put_manifest(_v1("keep", hashes[:2]))
        store.put_manifest(_v1("drop", hashes[2:]))
    (old,) = _pack_files(store.root)
    early = CheckpointStore(store.root)
    assert early.get_chunk(hashes[0]) == data[0]      # indexed the old pack
    before = store.stored_bytes()
    stats = store.gc(["keep"])
    assert stats["deleted_chunks"] == 4 and stats["kept_chunks"] == 2
    assert store.stored_bytes() == before - stats["deleted_bytes"] < before
    (new,) = _pack_files(store.root)
    assert new != old and not os.path.exists(old)
    assert store.stored_bytes() == os.path.getsize(new)
    for h, c in zip(hashes[:2], data[:2]):
        assert store.get_chunk(h) == c
        assert early.get_chunk(h) == c                # after a rescan
        assert CheckpointStore(store.root).get_chunk(h) == c
    with pytest.raises(FileNotFoundError):
        store.get_chunk(hashes[3])
    # a handle whose index still lists a collected chunk writes it again
    assert early.put_chunk(data[3])[2] is True
    assert store.gc(["keep"])["deleted_chunks"] == 1
    assert store.gc(["keep"])["deleted_chunks"] == 0  # nothing left to do
    size = os.path.getsize(new)
    assert store.gc([])["deleted_bytes"] == size
    assert _pack_files(store.root) == [] and store.stored_bytes() == 0


def test_gc_keeps_one_copy_of_a_chunk_written_twice(store):
    data = _chunks(2, seed=6)
    with store.packing():
        hashes = [store.put_chunk(c)[0] for c in data]
    # another writer that did not see the first pack writes one chunk again
    w = packs.PackWriter(os.path.join(store.root, "objects", "packs"))
    w.add(hashes[0], store._codec.compress(data[0]))
    w.publish()
    store.put_manifest(_v1("k", hashes))
    stats = store.gc(["k"])
    assert stats["deleted_chunks"] == 1 and stats["kept_chunks"] == 2
    assert len(_pack_files(store.root)) == 1
    fresh = CheckpointStore(store.root)
    assert [fresh.get_chunk(h) for h in hashes] == data


def test_job_bytes_match_its_packs(store, monkeypatch):
    """``new_bytes`` of each pipeline job, the packs it published and
    ``chunk_bytes`` over its new chunks are one number; the job counts the
    packs and its manifest in ``files_written``."""
    monkeypatch.setattr(packs, "PACK_BYTES", 64 * 1024)
    pipe = CheckpointPipeline(store, chunk_words=1024)
    seen: set = set()
    key = jax.random.PRNGKey(0)
    for i in range(3):
        w = jax.random.normal(jax.random.fold_in(key, i), (64 * 1024,))
        before = set(_pack_files(store.root))
        pipe.submit(f"k{i}", {"w": w})
        pipe.drain()
        stat = pipe.stats[-1]
        made = set(_pack_files(store.root)) - before
        m = store.get_manifest(f"k{i}")
        named = {h for lf in m["leaves"]
                 for h in (lf.get("chunks") or lf["delta"].values())}
        new = named - seen
        seen |= named
        size = sum(os.path.getsize(p) for p in made)
        assert len(made) > 1                       # rolled over at 64 KiB
        assert stat["new_bytes"] == size == store.chunk_bytes(new)
        assert stat["files_written"] == len(made) + 1
    pipe.close()


def _loose_store(root, pieces):
    """A store as written before packs: one ``<h>.zst`` file per chunk."""
    store = CheckpointStore(root)
    hashes = []
    for c in pieces:
        h = _hash(c)
        p = store._chunk_path(h)
        os.makedirs(os.path.dirname(p), exist_ok=True)
        _atomic_write(p, store._codec.compress(c))
        hashes.append(h)
    return hashes


def test_store_of_loose_chunks_restores_and_collects(tmp_path):
    root = str(tmp_path / "old")
    arr = np.arange(3 * 1024, dtype=np.float32)
    raw = arr.tobytes()
    pieces = [raw[i:i + 4096] for i in range(0, len(raw), 4096)]
    hashes = _loose_store(root, pieces + [b"orphan" * 700])
    old = CheckpointStore(root)
    old.put_manifest({"key": "old", "treedef": "", "meta": {},
                      "leaves": [{"path": "x", "dtype": "float32",
                                  "shape": [arr.size],
                                  "chunks": hashes[:-1]}]})
    store = CheckpointStore(root)
    np.testing.assert_array_equal(store.get_tree("old")["x"], arr)
    assert store.chunk_bytes(hashes[:1]) == os.path.getsize(
        store._chunk_path(hashes[0]))
    # a loose chunk is not written again into a pack
    assert store.put_chunk(pieces[0])[1:] == (0, False)
    assert _pack_files(root) == []
    # new chunks beside the loose ones: both kinds restore and count
    t = {"y": np.ones(2048, np.float32)}
    store.put_tree("new", t)
    assert len(_pack_files(root)) == 1
    st = store.stats()
    assert st["chunks"] == len(hashes) + 1
    stats = store.gc(["old", "new"])
    assert stats["deleted_chunks"] == 1
    assert not os.path.exists(store._chunk_path(hashes[-1]))
    np.testing.assert_array_equal(store.get_tree("old")["x"], arr)
    np.testing.assert_array_equal(store.get_tree("new")["['y']"], t["y"])
    assert store.gc(["new"])["deleted_chunks"] == len(pieces)
    assert store.stored_bytes() == os.path.getsize(_pack_files(root)[0])


def test_sharded_record_writes_packs_per_shard_pool(tmp_path):
    """A 2x2 mesh (4 virtual CPU devices) records into 4 store-shard
    pools: each gets packs, no loose chunk files, and the checkpoint
    restores; each job counts its packs and manifests."""
    code = textwrap.dedent(f"""
        import json, os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointPipeline, CheckpointStore
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        sh = NamedSharding(mesh, P("data", "model"))
        store = CheckpointStore({str(tmp_path / "store")!r})
        pipe = CheckpointPipeline(store, mesh=mesh, chunk_words=64)
        ws = []
        for i in range(2):
            w = jax.device_put(jnp.arange(64 * 32, dtype=jnp.float32)
                               .reshape(64, 32) * (i + 1), sh)
            ws.append(np.asarray(w))
            pipe.submit(f"k{{i}}", {{"w": w}})
        pipe.drain()
        files = [s["files_written"] for s in pipe.stats]
        pipe.close()
        out = CheckpointStore(store.root).get_tree("k1")
        ok = bool(np.array_equal(out["['w']"], ws[1]))
        print("RESULT " + json.dumps({{"files": files, "ok": ok}}))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    assert line, res.stderr[-3000:]
    out = json.loads(line[0][len("RESULT "):])
    assert out["ok"] is True
    root = str(tmp_path / "store")
    for hid in range(4):
        pool = os.path.join(root, "shards", str(hid), "objects")
        assert os.listdir(pool) == ["packs"]
        assert len(_pack_files(pool)) == 2          # one per checkpoint
    assert not os.path.exists(os.path.join(root, "objects", "packs"))
    # per checkpoint: a pack and a member manifest per shard, the stitch
    assert out["files"] == [9, 9]

