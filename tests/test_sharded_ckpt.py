"""Mesh-sharded record-replay: v4 manifests, resharding math, host-aware
planning/scheduling. In-process tests run on the default 1-device CPU; the
cross-mesh cases run in subprocesses with 8 forced host-platform devices
(conftest strips XLA_FLAGS from THIS process)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint.mesh import box_intersect, chunk_range
from repro.parallel.sharding import respec, spec_entries
from repro.replay import DynamicExecutor, Task, assign_hosts


# ------------------------------------------------------- pure-unit helpers --
def test_box_intersect():
    assert box_intersect([[0, 4], [0, 8]], [[2, 6], [4, 12]]) \
        == [[2, 4], [4, 8]]
    assert box_intersect([[0, 4]], [[4, 8]]) is None
    # scalars: full (empty-box) overlap, not None
    assert box_intersect([], []) == []


def test_chunk_range_envelope():
    # local 4x8 f32 leaf, 2 rows per 64-byte chunk -> 2 chunks
    lo, hi = chunk_range([[0, 4], [0, 8]], [[1, 2], [0, 8]], 4, 64, 2)
    assert (lo, hi) == (0, 1)
    lo, hi = chunk_range([[0, 4], [0, 8]], [[0, 4], [0, 8]], 4, 64, 2)
    assert (lo, hi) == (0, 2)


def test_spec_entries_json_form():
    from jax.sharding import PartitionSpec as P
    assert spec_entries(P("data", ("data", "model"), None)) \
        == ["data", ["data", "model"], None]
    assert spec_entries(None) is None


def test_respec_resolves_and_falls_back():
    import jax
    from jax.sharding import PartitionSpec as P
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    # recorded spec re-resolves verbatim when divisible
    assert respec(["data", "model"], (8, 8), mesh) == P("data", "model")
    # non-divisible dim drops the offending axis (replicates)
    assert respec(["data", "model"], (8, 6), mesh) == P("data", None)
    # axis missing from the target mesh is filtered out
    assert respec(["pod", "model"], (8, 8), mesh) == P(None, "model")
    # an axis never shards two dims
    sp = respec(["data", "data"], (8, 8), mesh)
    assert sp == P("data", None)


# -------------------------------------------------- host-aware scheduling --
def test_assign_hosts_lpt_balances():
    tasks = [Task(task_id=i, visits=[], est_cost_s=c)
             for i, c in enumerate([10.0, 9.0, 8.0, 2.0, 1.0])]
    assign_hosts(tasks, 2)
    loads = {0: 0.0, 1: 0.0}
    for t in tasks:
        loads[t.host] += t.est_cost_s
    # LPT keeps the spread under one task's cost; heaviest goes first
    assert abs(loads[0] - loads[1]) <= 10.0
    assert {t.host for t in tasks} == {0, 1}
    assert tasks[0].host != tasks[1].host   # two heaviest split


def test_executor_per_host_queues_complete_and_steal():
    ran = []
    tasks = [Task(task_id=i, visits=[], est_cost_s=1.0, host=1)
             for i in range(4)]          # every task homed on host 1
    ex = DynamicExecutor(tasks, lambda t, a, c: ran.append(t.task_id),
                         nworkers=2, n_hosts=2)
    done = ex.run()                      # host-0 workers must steal
    assert sorted(done) == [0, 1, 2, 3]
    assert sorted(ran) == [0, 1, 2, 3]


def test_executor_retry_requeues_to_home_host():
    attempts = {}

    def flaky(t, a, c):
        attempts[t.task_id] = a
        if t.task_id == 1 and a == 1:
            raise RuntimeError("boom")
        return a

    tasks = [Task(task_id=i, visits=[], host=i % 2) for i in range(3)]
    done = DynamicExecutor(tasks, flaky, 2, n_hosts=2).run()
    assert done[1][0] == 2               # second attempt won


# ------------------------------------------- v4 manifests on a tiny mesh --
def _mesh1():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(1), ("data",))


def _sharded_store(tmp_path, n_ckpts=2):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import CheckpointPipeline, CheckpointStore
    store = CheckpointStore(os.path.join(tmp_path, "store"))
    mesh = _mesh1()
    pipe = CheckpointPipeline(store, async_stage=False, mesh=mesh)
    trees = []
    for i in range(n_ckpts):
        w = jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8) + i,
            NamedSharding(mesh, P("data", None)))
        tree = {"w": w, "step": i}
        pipe.submit(f"train@{i}.0", tree, block=True)
        trees.append({"w": np.asarray(jax.device_get(w)),
                      "step": np.int64(i)})
    pipe.close()
    return store, mesh, trees


def test_sharded_record_roundtrip_and_delta_chain(tmp_path):
    store, mesh, trees = _sharded_store(str(tmp_path))
    m0 = store.resolve_manifest("train@0.0")
    m1 = store.resolve_manifest("train@1.0")
    assert m0["kind"] == "sharded" and m0["ckpt_kind"] == "full"
    assert m1["ckpt_kind"] == "delta" and m1["parent"] == "train@0.0"
    # member manifests chain per shard
    mem1 = m1["members_resolved"][0]
    assert mem1["parent"] == "train@0.0.shard0"
    assert mem1["store_shard"] == 0
    for i, truth in enumerate(trees):
        like = {"w": np.empty((8, 8), np.float32), "step": np.int64(0)}
        out = store.get_tree(f"train@{i}.0", like=like)
        assert np.array_equal(out["w"], truth["w"])
        assert int(out["step"]) == i


def test_restore_sharded_tree_same_mesh(tmp_path):
    from repro.checkpoint import restore_sharded_tree
    store, mesh, trees = _sharded_store(str(tmp_path))
    out = restore_sharded_tree(store, "train@1.0", mesh)
    assert np.array_equal(np.asarray(out["['w']"]), trees[1]["w"])


def test_stats_report_sharded_members(tmp_path):
    store, _, _ = _sharded_store(str(tmp_path))
    st = store.stats(keys=store.list_keys(), per_key=True)
    assert st["sharded_manifests"] == 2
    info = st["per_key"]["train_at_1.0"]
    assert 0 in {int(h) for h in info["shards"]}
    assert info["shards"][list(info["shards"])[0]]["chunks"] >= 1


def test_gc_keeps_live_shard_member_closure(tmp_path):
    """Satellite fix: shard members are part of the global manifest's
    closure — GC with only the DELTA tip live must keep the parent full's
    member chunks alive too."""
    store, mesh, trees = _sharded_store(str(tmp_path))
    res = store.gc(live_keys=["train@1.0"])
    assert res["deleted_chunks"] == 0, res
    like = {"w": np.empty((8, 8), np.float32), "step": np.int64(0)}
    out = store.get_tree("train@1.0", like=like)
    assert np.array_equal(out["w"], trees[1]["w"])
    # dropping the tip reclaims the whole chain, shard pools included
    res = store.gc(live_keys=[])
    assert res["deleted_chunks"] > 0


def test_sharded_restore_read_stats(tmp_path):
    store, _, _ = _sharded_store(str(tmp_path))
    stats = {}
    like = {"w": np.empty((8, 8), np.float32), "step": np.int64(0)}
    store.get_tree("train@1.0", like=like, stats_out=stats)
    assert stats["chunks_read"] >= 1
    assert sum(stats["bytes_by_shard"].values()) > 0


def test_warm_start_from_sharded_manifest_raises(tmp_path):
    from repro.checkpoint import CheckpointPipeline
    store, _, _ = _sharded_store(str(tmp_path))
    pipe = CheckpointPipeline(store, async_stage=False)
    manifest = store.resolve_manifest("train@1.0")
    with pytest.raises(ValueError):
        pipe.warm_start("train", "train@1.0", manifest, {})
    pipe.close()


# -------------------------------------------------- host-aware plan costs --
def test_plan_uses_per_shard_read_rates(tmp_path):
    from repro.replay import build_plan
    store, _, _ = _sharded_store(str(tmp_path))
    store.put_meta("run", {"epochs": [0, 1], "main_loop": "epochs",
                           "num_epochs": 2})

    def plan_with(bps):
        calib = {"read_bps": 1e9, "hop_s": 0.0}
        if bps is not None:
            calib["shard_read_bps"] = {"0": bps}
        store.put_meta("store_calib", calib)
        return build_plan(str(tmp_path), probed=frozenset(), store=store,
                          epochs=[0, 1])

    slow = plan_with(1e3)
    fast = plan_with(1e9)
    s_cost = sum(s.restore_cost_s for s in slow.segments)
    f_cost = sum(s.restore_cost_s for s in fast.segments)
    assert s_cost > f_cost * 100       # slow shard dominates the estimate
    assert all(s.hosts >= 1 for s in slow.segments)
    assert slow.mesh.get("n_store_shards") == 1   # from recorded mesh meta
    # round-trips through save/load (tolerant from_dict)
    loaded = type(slow).from_dict(slow.to_dict())
    assert loaded.mesh == slow.mesh
    assert [s.hosts for s in loaded.segments] \
        == [s.hosts for s in slow.segments]


# ----------------------------------------------- encodings on a mesh --------
def test_sharded_error_bound_slots(tmp_path):
    """The adaptive encoding selector composes with the sharded (v4) path:
    bounded slots land as q4/q8 wire chunks in the member manifests, deltas
    carry denc, the resolved member chain inherits enc, and restores stay
    within the declared bound (exact slots bit-identical)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint import (CheckpointPipeline, CheckpointStore,
                                  restore_sharded_tree)
    store = CheckpointStore(os.path.join(str(tmp_path), "store"))
    mesh = _mesh1()
    pipe = CheckpointPipeline(store, async_stage=False, mesh=mesh,
                              chunk_words=16, error_bounds={"mu": 1e-2})
    rng = np.random.default_rng(7)
    mus, ws = [], []
    for i in range(2):
        mu = (0.02 * rng.normal(size=(8, 8))).astype(np.float32)
        w = rng.normal(size=(8, 8)).astype(np.float32)
        sh = NamedSharding(mesh, P("data", None))
        pipe.submit(f"train@{i}.0", {
            "mu": jax.device_put(jnp.asarray(mu), sh),
            "w": jax.device_put(jnp.asarray(w), sh)}, block=True)
        mus.append(mu)
        ws.append(w)
    pipe.close()
    for i in range(2):
        like = {"mu": np.empty((8, 8), np.float32),
                "w": np.empty((8, 8), np.float32)}
        out = store.get_tree(f"train@{i}.0", like=like)
        assert np.max(np.abs(out["mu"] - mus[i])) <= 1e-2
        assert np.array_equal(out["w"], ws[i])
    # member manifests carry the wire encodings (paths gain ::shard<h>)
    m0 = store.resolve_manifest("train@0.0")
    lf0 = {l["path"]: l for l in m0["members_resolved"][0]["leaves"]}
    assert lf0["['mu']::shard0"]["leaf_enc"] == "eb:0.01"
    assert set(lf0["['mu']::shard0"]["enc"]) <= {"q4", "q8", "q4+z", "q8+z"}
    assert all(e == "raw" for e in lf0["['w']::shard0"].get("enc", []))
    # the delta member records denc; the resolved chain inherits enc
    raw1 = store.get_manifest("train@1.0.shard0")
    rlf = {l["path"]: l for l in raw1["leaves"]}["['mu']::shard0"]
    assert rlf.get("delta") and rlf.get("denc")
    assert set(rlf["denc"].values()) <= {"q4", "q8", "q4+z", "q8+z"}
    m1 = store.resolve_manifest("train@1.0")
    lf1 = {l["path"]: l for l in m1["members_resolved"][0]["leaves"]}
    assert set(lf1["['mu']::shard0"]["enc"]) <= {"q4", "q8", "q4+z", "q8+z"}
    # mesh-placed restore decodes the wire chunks too
    out = restore_sharded_tree(store, "train@1.0", mesh)
    assert np.max(np.abs(np.asarray(out["['mu']"]) - mus[1])) <= 1e-2
    assert np.array_equal(np.asarray(out["['w']"]), ws[1])
    # stats/encoding_mix see through the v4 indirection
    mix = store.encoding_mix("train@1.0")
    assert any(e.startswith("q") for e in mix)
    st = store.stats(keys=store.list_keys(), per_key=True)
    encc = st["per_key"]["train_at_1.0"]["enc_counts"]
    assert any(e.startswith("q") for e in encc)


@pytest.mark.slow
def test_encoded_slots_cross_mesh_restore_within_bound():
    """q4/q8-encoded slots recorded on a (2, 4) mesh restore within their
    declared bound on (4, 2), (1, 8) and unsharded; the exact slot stays
    bit-identical across every resharding."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import (CheckpointPipeline, CheckpointStore,
                                      restore_sharded_tree)
        devs = jax.devices()
        mesh = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))
        store = CheckpointStore("/tmp/t_sh8enc/store")
        pipe = CheckpointPipeline(store, async_stage=False, mesh=mesh,
                                  chunk_words=64,
                                  error_bounds={"mu": 1e-2})
        rng = np.random.default_rng(11)
        def state(i):
            mu = (0.02 * rng.normal(size=(64, 32))).astype(np.float32)
            w = rng.normal(size=(64, 32)).astype(np.float32)
            sh = NamedSharding(mesh, P("data", "model"))
            return ({"mu": jax.device_put(jnp.asarray(mu), sh),
                     "w": jax.device_put(jnp.asarray(w), sh)},
                    {"mu": mu, "w": w})
        truth = None
        for i in range(2):
            tree, truth = state(i)
            pipe.submit(f"train@{i}.0", tree, block=True)
        assert store.resolve_manifest("train@1.0")["ckpt_kind"] == "delta"
        m1 = store.resolve_manifest("train@1.0")
        for mem in m1["members_resolved"].values():
            for l in mem["leaves"]:
                if l["path"].startswith("['mu']"):
                    assert set(l["enc"]) <= {"q4", "q8",
                                             "q4+z", "q8+z"}, l
        like = {k: np.empty_like(v) for k, v in truth.items()}
        got = store.get_tree("train@1.0", like=like)
        assert np.max(np.abs(got["mu"] - truth["mu"])) <= 1e-2
        assert np.array_equal(got["w"], truth["w"])
        for shape in ((4, 2), (1, 8)):
            m2 = Mesh(np.array(devs).reshape(shape), ("data", "model"))
            out = restore_sharded_tree(store, "train@1.0", m2)
            mu = np.asarray(jax.device_get(out["['mu']"]))
            assert np.max(np.abs(mu - truth["mu"])) <= 1e-2, shape
            w = np.asarray(jax.device_get(out["['w']"]))
            assert np.array_equal(w, truth["w"]), shape
        pipe.close()
        print("SH8ENC_OK")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    subprocess.run([sys.executable, "-c", "import shutil; "
                    "shutil.rmtree('/tmp/t_sh8enc', ignore_errors=True)"])
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert "SH8ENC_OK" in out.stdout, out.stderr[-3000:]


# ----------------------------------------------- 8-device cross-mesh cases --
@pytest.mark.slow
def test_record_2x4_restores_bitwise_on_other_meshes():
    """Record on (2, 4); restore bit-identically on (4, 2), (1, 8) and
    unsharded; resharding a leaf mid-run forces a FULL manifest."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.checkpoint import (CheckpointPipeline, CheckpointStore,
                                      restore_sharded_tree)
        devs = jax.devices()
        mesh = Mesh(np.array(devs).reshape(2, 4), ("data", "model"))
        store = CheckpointStore("/tmp/t_sh8/store")
        pipe = CheckpointPipeline(store, async_stage=False, mesh=mesh)
        base = jnp.sin(jnp.arange(64 * 32, dtype=jnp.float32)
                       ).reshape(64, 32)
        def state(i, spec=P("data", "model")):
            return {"w": jax.device_put(base * (1.0 + 0.001 * i),
                                        NamedSharding(mesh, spec)),
                    "b": jax.device_put(base[0] * (2.0 + 0.001 * i),
                                        NamedSharding(mesh, P("model")))}
        for i in range(2):
            pipe.submit(f"train@{i}.0", state(i), block=True)
        assert store.resolve_manifest("train@1.0")["ckpt_kind"] == "delta"
        truth = {k: np.asarray(jax.device_get(v))
                 for k, v in state(1).items()}
        like = {k: np.empty_like(v) for k, v in truth.items()}
        got = store.get_tree("train@1.0", like=like)
        assert all(np.array_equal(got[k], truth[k]) for k in truth)
        for shape in ((4, 2), (1, 8)):
            m2 = Mesh(np.array(devs).reshape(shape), ("data", "model"))
            out = restore_sharded_tree(store, "train@1.0", m2)
            for k in truth:
                arr = np.asarray(jax.device_get(out[f"['{k}']"]))
                assert np.array_equal(arr, truth[k]), (shape, k)
        # selective reads: a same-layout sharded restore touches every
        # store shard but reads each byte once
        stats = {}
        st_like = state(1)
        store.get_tree("train@1.0", like=st_like, stats_out=stats)
        assert len(stats["bytes_by_shard"]) == 8
        total = sum(v.nbytes for v in truth.values())
        assert sum(stats["bytes_by_shard"].values()) <= 2 * total
        # resharding a leaf mid-run changes the layout -> forced FULL
        pipe.submit("train@2.0", state(2, spec=P(None, "model")),
                    block=True)
        assert store.resolve_manifest("train@2.0")["ckpt_kind"] == "full"
        pipe.close()
        print("SH8_OK")
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    subprocess.run([sys.executable, "-c", "import shutil; "
                    "shutil.rmtree('/tmp/t_sh8', ignore_errors=True)"])
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert "SH8_OK" in out.stdout, out.stderr[-3000:]
