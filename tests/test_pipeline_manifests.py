"""Pinned manifests of the record path: one fixed checkpoint sequence on a
seeded tree, recorded flat and on a 2x2 mesh, in every record mode. Each
manifest's canonical JSON and each checkpoint's stat keys are compared
with values recorded once, so a change to the record path that alters a
manifest byte or a stat key fails here.

The sequence: a full checkpoint, two deltas, a leaf added (full), a dtype
change (full), a non-blocking submit refused by a full queue, then two
deltas. The tree holds an exact f32 leaf, a ``quantize_slots`` q8 leaf, an
``error_bounds`` leaf whose chunks span q4, q8 and raw, a replicated leaf,
a 64-bit host leaf and a zero-byte leaf. ``entropy=False`` keeps codec
choices out of the manifests."""
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointPipeline, CheckpointStore

MODES = ("async", "overlap", "sync")


def _tree(step: int, place, extra=None):
    rng = np.random.default_rng(0)
    w = rng.standard_normal((64, 96)).astype(np.float32)
    q = rng.standard_normal((32, 64)).astype(np.float32)
    scale = np.array([0.1, 1.0, 10.0], np.float32)[(np.arange(32) // 4) % 3]
    e = (rng.uniform(-1.0, 1.0, (32, 64)) * scale[:, None]).astype(np.float32)
    if step >= 1:
        w[: 8 * step] += 1.0
    if step >= 2:
        q[:4] *= 2.0
        e[:8] *= 0.5
    tree = {"w": place(w), "q": place(q), "e": place(e),
            "rep": place(np.arange(64, dtype=np.float32) + min(step, 2),
                         replicated=True),
            "step": np.int64(step),
            "empty": place(np.zeros((0,), np.float32), replicated=True)}
    if extra is not None:
        tree["extra"] = place(extra)
    return tree


class _FullQueue:
    def submit_job(self, key, fn, block=True):
        return False


def _canon(m) -> str:
    return hashlib.sha256(
        json.dumps(m, sort_keys=True).encode()).hexdigest()


def record_sequence(root: str, mode: str, mesh=None) -> dict:
    """Record the fixed sequence into a new store under ``root``; return
    {"manifests": {key: sha256}, "stats": [[key, ...]], "submits":
    [[key, ...] | None]}."""
    if mesh is None:
        def place(a, replicated=False):
            return jnp.asarray(a)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        def place(a, replicated=False):
            spec = P() if replicated else P("data", "model")
            return jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))
    store = CheckpointStore(os.path.join(root, mode))
    pipe = CheckpointPipeline(store, chunk_words=256, mesh=mesh,
                              async_stage=mode != "sync",
                              overlap=mode == "overlap",
                              quantize_slots=("q",),
                              error_bounds={"e": 0.01}, entropy=False)
    extra = np.linspace(-1.0, 1.0, 16 * 64, dtype=np.float32).reshape(16, 64)
    steps = [(0, None), (1, None), (2, None), (2, extra),
             (2, extra.astype(jnp.bfloat16)), (3, extra.astype(jnp.bfloat16)),
             (3, extra.astype(jnp.bfloat16)), (4, extra.astype(jnp.bfloat16))]
    submits = []
    for i, (step, ex) in enumerate(steps):
        tree = _tree(step, place, ex)
        if i == 5:                       # refused by a full queue
            pipe.drain()
            real, pipe.writer = pipe.writer, _FullQueue()
            s = pipe.submit(f"k{i}", tree, meta={"i": i}, scope="train",
                            block=False)
            pipe.writer = real
        else:
            s = pipe.submit(f"k{i}", tree, meta={"i": i}, scope="train")
        submits.append(None if s is None else sorted(s))
        pipe.drain()
    stats = [sorted(s) for s in pipe.stats]
    pipe.close()
    manifests = {k: _canon(store.get_manifest(k)) for k in store.list_keys()}
    return {"manifests": manifests, "stats": stats, "submits": submits}


_FLAT_STAT = [
    "changed_chunks", "compress_s", "copy_s", "entropy_s", "file_s",
    "files_written", "full_every", "hash_s", "key", "kind",
    "logical_bytes", "materialize_s", "new_bytes", "new_chunks",
    "overlap", "parent", "queue_wait_s", "stored_bytes", "submit_stall_s",
    "total_chunks", "transferred_bytes"]
_FLAT_SUBMIT = [
    "changed_chunks", "key", "kind", "logical_bytes", "overlap", "parent",
    "submit_stall_s", "total_chunks", "transferred_bytes"]
_MESH_STAT = [
    "changed_chunks", "compress_s", "copy_s", "entropy_s", "file_s",
    "files_written", "full_every", "hash_s", "key", "kind",
    "logical_bytes", "materialize_s", "n_store_shards", "new_bytes",
    "new_chunks", "overlap", "parent", "queue_wait_s", "shard_bytes",
    "shard_stall_s", "shard_write_s", "sharded", "stitched",
    "submit_stall_s", "total_chunks", "transferred_bytes"]
_MESH_SUBMIT = [
    "changed_chunks", "key", "kind", "logical_bytes", "n_store_shards",
    "overlap", "parent", "shard_stall_s", "sharded", "submit_stall_s",
    "total_chunks", "transferred_bytes"]

FLAT_MANIFESTS = {
    "k0":
        "9c994121c8e9aae1c907856950af906036e9a2b452505155d0a13c3414a3707d",
    "k1":
        "c3072d99a986d599489e82d3e986060c81b774c99717debbdd23c93b928d8ca0",
    "k2":
        "31383b3d60b31eb25d52e9e940bcc22f22e5962b83fe8a65515e8cc15f29b70e",
    "k3":
        "8ab866dd692b635bc2287745abc27003df63f1429f78e181c9d3671dd6d50319",
    "k4":
        "080d0a1ebab188f9673db91d58fffe62b88f21af5697e209299d39b10ec649ee",
    "k6":
        "c2cf112f5c6e586efd1a05b71a1624c00f1c2e3c2a21e2153173687de2c0b04e",
    "k7":
        "0890ec7882d126aabeee63c2e135c131224511705d47388d09d8fdfab74300b9",
}
MESH_MANIFESTS = {
    "k0":
        "444122e96d7e17429d90db2c707e745b48bc36537690b2e0d694dc70caa039a1",
    "k0.shard0":
        "0ef8b7abe25faffde4b8883c23c70bde86c6c58b316d5c1279f6a61761537c93",
    "k0.shard1":
        "4a98096b1ffe4ede550e79cbbb4b399d8d4c22af485418db9e3a68d5e8e6b1ca",
    "k0.shard2":
        "dcc17b894004795f8c13bbf3b1ac687eefc5e09a580d45248c31f5e44365341f",
    "k0.shard3":
        "ffc12007d20319d9274b22719420ebcb906e8112018c4578832858630aa197b9",
    "k1":
        "08456a9807ce5328a98a0a40081197924722040d3f9b4f8c77131236cdec1e9e",
    "k1.shard0":
        "b874759d1623c9175bb0f5e1360d04d5f25e0b0c36a443fe0f5f319c33419bf6",
    "k1.shard1":
        "22b4fb1b2f195b6020706c1bfde67871c275168a9aab83f049e7adeb9473db29",
    "k1.shard2":
        "25fc011ca88f790831511f8bf8b935e3e2a60f5bca4f1d6a6408b418adca5fdd",
    "k1.shard3":
        "b96416fcbfd830d7f2fe0ffb3aa87d9ed4c0a146117f7e2d2b1953569a5704e0",
    "k2":
        "20dc0941dfe8398caf0e397ee5e3b717e6c3643a568bc585dca6c06e24674649",
    "k2.shard0":
        "e32f8e5d739696f3e8ec66e232db4971917989565e5be95ee590cbc750e48e42",
    "k2.shard1":
        "695cb3ebc695575629254b704d6e40185dd3b1a60ba1b971cdb042544349a6a6",
    "k2.shard2":
        "35062eba194108e955f2ed15709c38af9e2df69c3891fc35565a74ebc9764e77",
    "k2.shard3":
        "b975ba759b4f3380fcccc4419b592b9151340f113e84f6f52615113288c5ad73",
    "k3":
        "e05ec7b1448eba2a9ff47dcbae25efb706f56ff6ac60ccc4c520439f8107fbd9",
    "k3.shard0":
        "c61f6ee25cd7b36b9ccb776029e779c3dd74326332c1f381b36f25236e0ecc62",
    "k3.shard1":
        "3add8393059f3475ddd9f332fb14f6dff41fabfbb5774f50274edd4f76de4379",
    "k3.shard2":
        "95a299f2c858d65bacbaffd8c01f350df081d5ca305446698aa2022417774a09",
    "k3.shard3":
        "846d59c8d10bbd672066a215e5e21858366240604a76f4daaf969e34c1d47670",
    "k4":
        "2c544c8f4996d052add71a057c3e36fc630ae7eee0739dfd05aae8cbdd31d7bc",
    "k4.shard0":
        "410bdf6452b1b31d603ef83194fb3d0014a3191fb631998efaa03e95a17401e4",
    "k4.shard1":
        "d136117600ef46561cb981a723b009e0a51fedecc654cdad1da39a269ee5a848",
    "k4.shard2":
        "ae4861e61f7a48df3a9a98c6a883f77fbb26d4534f89a00f011c39532c759446",
    "k4.shard3":
        "ed1688e7e2783affb0d78f37aca77a32fc6e512a0e63fe9d65b1e1c0f1abf9fb",
    "k6":
        "25fb5764f617253fb13052e6e22e12948911b28936b70f6bcdd39d2007e110a6",
    "k6.shard0":
        "b5a15f29bf10292d2f6dd46a7d9ebdf2c747ab9d75ac759f0cbb28bd41a1327a",
    "k6.shard1":
        "50b38c58d0075c6a81a9c43d50bb11c14a1215fe6be7b7bd2fbf5779c0767853",
    "k6.shard2":
        "8e8860eb3c149b751f79e0971af2f0579a0b76209fbf0d4a1eac74843842892c",
    "k6.shard3":
        "5029a71bf8a755bfd522f537bd21666b21eaad71d459dd0c3176c6be35ca70a0",
    "k7":
        "2197bdb9c3ac61a1fd618d3c1ac2a8f5f73dc5e216748da22366c56f83ae6488",
    "k7.shard0":
        "335f7f59b657d1a31bdc21a9dc2462746d809677252faa1c448c9240c2686f80",
    "k7.shard1":
        "b718399e35daeffd2f8782ce5026abd18456d2bfbf97ea6a0ae3544d6bbd7c8a",
    "k7.shard2":
        "49eb719cd819f4d32c19eaa34c15858db03ee480bf1dd7d650fd77f92fa4c32b",
    "k7.shard3":
        "1c213f110ca8491285224cb05da269fce9580175ceec5f079ff88de2b41bf9fb",
}


def _check(got: dict, manifests: dict, stat_keys: list, submit_keys: list):
    assert got["manifests"] == manifests
    assert got["stats"] == [stat_keys] * 7
    assert got["submits"] == [submit_keys] * 5 + [None] \
        + [submit_keys] * 2


def test_flat_manifests_are_pinned(tmp_path):
    for mode in MODES:
        _check(record_sequence(str(tmp_path), mode), FLAT_MANIFESTS,
               _FLAT_STAT, _FLAT_SUBMIT)


def test_mesh_manifests_are_pinned(tmp_path):
    """The same sequence on a 2x2 mesh of 4 forced CPU devices: v3 member
    manifests per store shard and a v4 stitch per checkpoint."""
    code = textwrap.dedent(f"""
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from jax.sharding import Mesh
        sys.path.insert(0, {os.path.dirname(__file__)!r})
        from test_pipeline_manifests import MODES, record_sequence
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
        out = {{m: record_sequence({str(tmp_path)!r}, m, mesh)
               for m in MODES}}
        print("RESULT " + json.dumps(out))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    line = [ln for ln in res.stdout.splitlines()
            if ln.startswith("RESULT ")]
    assert line, res.stderr[-3000:]
    out = json.loads(line[0][len("RESULT "):])
    for mode in MODES:
        _check(out[mode], MESH_MANIFESTS, _MESH_STAT, _MESH_SUBMIT)
