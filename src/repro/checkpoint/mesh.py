"""Mesh-sharded record/restore geometry for the checkpoint pipeline.

Record side: ``device_maps`` + ``owned_shards`` enumerate, per pytree leaf,
the disjoint device shards a mesh owns (``addressable_shards`` filtered to
``replica_id == 0`` is an exact cover of the global array) together with
each shard's global index bounds and owning STORE SHARD (simulated host).
The pipeline runs the fused fingerprint+gather pass on each shard's own
device buffer and writes its chunks to that host's pool — bytes never
cross a device boundary except device -> owning host.

Restore side: ``stitch_tree`` rebuilds a tree from a v4 stitching manifest.
Given a target ``NamedSharding`` (a sharded `like` leaf, or a spec
re-resolved on a new mesh via ``parallel.sharding.respec``), each target
shard is assembled via ``jax.make_array_from_callback`` from ONLY the
recorded chunks its index box overlaps — chunk ranges are computed from the
box's byte envelope in the recorded shard's local row-major layout — so an
N-host recording restores onto an M-host (or single-host) mesh reading just
what the new layout needs.
"""
from __future__ import annotations

from typing import Any, Optional

import numpy as np

from repro.checkpoint.store import np_dtype


# ------------------------------------------------------------ record side --
def mesh_meta(mesh, shard_axes=()) -> dict:
    """Serializable description of the recording layout for manifest v4 /
    store meta: mesh axes in order, the store-shard axes, and counts."""
    names = [str(a) for a in mesh.axis_names]
    sa = [str(a) for a in (shard_axes or names)]
    n_store = 1
    for a in sa:
        n_store *= int(mesh.shape[a])
    return {"axes": [[a, int(mesh.shape[a])] for a in names],
            "shard_axes": sa,
            "n_devices": int(mesh.devices.size),
            "n_store_shards": n_store}


def device_maps(mesh, shard_axes=()) -> tuple[dict, dict]:
    """({device_id: device_ordinal}, {device_id: store_shard}).

    The device ordinal is the device's flat index in ``mesh.devices`` (the
    stable shard id manifests record). The store shard is the flat index of
    the device's coordinates restricted to ``shard_axes`` — the default
    ``()`` means ALL mesh axes: one store shard per device, the
    max-parallel simulated-host granularity; a real multi-host deployment
    passes the axes that map onto hosts."""
    names = [str(a) for a in mesh.axis_names]
    sa = [str(a) for a in (shard_axes or names)]
    for a in sa:
        if a not in names:
            raise ValueError(f"ckpt_shard_axes entry {a!r} is not a mesh "
                             f"axis (mesh axes: {names})")
    dims = mesh.devices.shape
    sel = [names.index(a) for a in sa]
    ords: dict[int, int] = {}
    hosts: dict[int, int] = {}
    for flat, idx in enumerate(np.ndindex(*dims)):
        d = mesh.devices[idx]
        ords[d.id] = flat
        h = 0
        for axpos in sel:
            h = h * dims[axpos] + idx[axpos]
        hosts[d.id] = h
    return ords, hosts


def owned_shards(leaf, ords: dict, hosts: dict,
                 process_index: Optional[int] = None,
                 anchor: tuple[int, int] = (0, 0)) -> list[dict]:
    """Disjoint owner shards of one leaf: [{sid, hid, bounds, data}, ...]
    sorted by sid, where ``bounds`` is the shard's global index box
    ``[[lo, hi), ...]`` and ``data`` its single-device buffer.

    jax arrays placed on the mesh cover exactly via their
    ``replica_id == 0`` addressable shards (a replicated leaf has ONE owner
    shard). Host numpy/python leaves — and arrays living off the mesh —
    fall back to a single full shard owned by store shard 0.

    Multi-process mode (``process_index`` set): a mesh leaf contributes
    exactly the replica-0 shards addressable from THIS process — possibly
    none (the union across the fleet is the same exact cover the
    single-process path enumerates). Host / off-mesh leaves are SPMD-
    replicated values, so only process 0 publishes them, under ``anchor``
    — the (sid, hid) of process 0's lowest-ordinal mesh device — keeping
    every byte inside a pool its writer owns."""
    shards = getattr(leaf, "addressable_shards", None)
    if shards is not None:
        out = []
        on_mesh = True
        for sh in shards:
            did = sh.device.id
            if did not in ords:
                on_mesh = False
                break
            if getattr(sh, "replica_id", 0) != 0:
                continue
            bounds = [[int(s.start or 0),
                       int(s.stop if s.stop is not None else dim)]
                      for s, dim in zip(sh.index, leaf.shape)]
            out.append({"sid": ords[did], "hid": hosts[did],
                        "bounds": bounds, "data": sh.data})
        if on_mesh and (out or process_index is not None):
            out.sort(key=lambda e: e["sid"])
            return out
    if process_index is not None and process_index != 0:
        return []
    sid, hid = anchor if process_index is not None else (0, 0)
    full = [[0, int(d)] for d in getattr(leaf, "shape", ())]
    return [{"sid": sid, "hid": hid, "bounds": full, "data": leaf}]


def local_anchor(mesh, ords: dict, hosts: dict,
                 process_index: int) -> tuple[int, int]:
    """(sid, hid) of this process's lowest-ordinal mesh device — the pool
    host/off-mesh leaves are filed under in multi-process record. Falls
    back to (0, 0) for a process with no mesh devices."""
    best = None
    for d in mesh.devices.flat:
        if getattr(d, "process_index", 0) != process_index:
            continue
        cand = (ords[d.id], hosts[d.id])
        if best is None or cand < best:
            best = cand
    return best if best is not None else (0, 0)


def leaf_spec_entries(leaf) -> Optional[list]:
    """The recorded physical PartitionSpec of a jax array under a
    NamedSharding, in ``parallel.sharding.spec_entries`` serialized form
    (None for host / unsharded leaves) — what a resharded restore
    re-resolves on the target mesh."""
    sh = getattr(leaf, "sharding", None)
    spec = getattr(sh, "spec", None)
    if spec is None:
        return None
    from repro.parallel.sharding import spec_entries
    return spec_entries(spec)


# ----------------------------------------------------------------- box math --
def box_intersect(a, b) -> Optional[list]:
    """Intersection of two index boxes ([] = scalar box, full overlap);
    None when empty."""
    out = []
    for (al, ah), (bl, bh) in zip(a, b):
        lo, hi = max(int(al), int(bl)), min(int(ah), int(bh))
        if lo >= hi:
            return None
        out.append([lo, hi])
    return out


def chunk_range(rec_bounds, box, itemsize: int, chunk_bytes: int,
                n_chunks: int) -> tuple[int, int]:
    """Chunk index range [lo, hi) of a recorded shard's chunking that
    covers ``box`` (global coords, inside ``rec_bounds``): the byte
    envelope from the first to the last element of the box in the shard's
    local row-major layout. Exact for leading-dim sharding; a conservative
    superset when the box is a strided sub-block."""
    local = [hi - lo for lo, hi in rec_bounds]
    strides = []
    s = 1
    for d in reversed(local):
        strides.append(s)
        s *= d
    strides.reverse()
    first = sum((bl - rl) * st
                for (bl, _), (rl, _), st in zip(box, rec_bounds, strides))
    last = sum((bh - 1 - rl) * st
               for (_, bh), (rl, _), st in zip(box, rec_bounds, strides))
    lo = (first * itemsize) // chunk_bytes
    hi = -(-((last + 1) * itemsize) // chunk_bytes)
    return max(0, lo), min(n_chunks, hi)


# ---------------------------------------------------------------- restore --
def _member_leaves(resolved_member: dict) -> dict:
    """{member leaf path: leaf} with a one-shot cache on the member."""
    cached = resolved_member.get("_by_path")
    if cached is None:
        cached = {lf["path"]: lf for lf in resolved_member["leaves"]}
        resolved_member["_by_path"] = cached
    return cached


def _chunk_native_bytes(chunk_words: int, dtype: str) -> int:
    from repro.kernels.ops import native_bytes_per_word
    return int(chunk_words) * native_bytes_per_word(dtype)


def _note_read(stats: Optional[dict], hid: int, nbytes: int, n: int):
    if stats is None:
        return
    stats["chunks_read"] = stats.get("chunks_read", 0) + n
    by = stats.setdefault("bytes_by_shard", {})
    by[hid] = by.get(hid, 0) + nbytes


def _read_shard_range(store, mleaf: dict, store_shard: int, c_lo: int,
                      c_hi: int, dt: np.dtype,
                      stats: Optional[dict]) -> bytes:
    """Decoded native bytes of chunks [c_lo, c_hi) of one recorded device
    shard (encoded chunks — q8 / q4 / entropy-compressed — decode
    transparently, as in the flat get_tree)."""
    enc = mleaf.get("enc")
    chunks = mleaf["chunks"]
    parts = []
    for i in range(c_lo, c_hi):
        raw = store.get_chunk(chunks[i], shard=store_shard)
        if enc and enc[i] != "raw":
            from repro.kernels.ops import decode_wire_chunk
            raw = decode_wire_chunk(raw, enc[i], dt)
        parts.append(raw)
    out = b"".join(parts)
    _note_read(stats, store_shard, len(out), c_hi - c_lo)
    return out


def _read_box(store, mleaf: dict, store_shard: int, rec_bounds, box,
              dt: np.dtype, chunk_words: int,
              stats: Optional[dict]) -> np.ndarray:
    """The sub-array ``box`` (global coords) of one recorded device shard,
    reading only the chunks covering the box's byte envelope."""
    cn = _chunk_native_bytes(chunk_words, str(dt))
    nbytes = int(mleaf["nbytes"])
    n_chunks = int(mleaf["n_chunks"])
    c_lo, c_hi = chunk_range(rec_bounds, box, dt.itemsize, cn, n_chunks)
    raw = _read_shard_range(store, mleaf, store_shard, c_lo, c_hi, dt, stats)
    start = c_lo * cn
    flat = np.zeros(nbytes, dtype=np.uint8)
    flat[start:start + len(raw)] = np.frombuffer(raw, np.uint8)[:nbytes - start]
    local = flat.view(dt).reshape([hi - lo for lo, hi in rec_bounds])
    rel = tuple(slice(bl - rl, bh - rl)
                for (bl, bh), (rl, _) in zip(box, rec_bounds))
    # reshape after ascontiguousarray: it promotes 0-d results to (1,),
    # which would break the 0-d assignment for scalar leaves downstream
    return np.ascontiguousarray(local[rel]).reshape(
        tuple(hi - lo for lo, hi in box))


def _stitch_leaf_full(store, resolved: dict, leaf: dict,
                      stats: Optional[dict]) -> np.ndarray:
    """Full numpy stitch of one v4 leaf: every recorded shard's bytes land
    in its global bounds box."""
    dt = np_dtype(leaf["dtype"])
    out = np.empty(tuple(leaf["shape"]), dtype=dt)
    members = resolved["members_resolved"]
    for se in leaf["shards"]:
        mleaf = _member_leaves(members[int(se["hid"])])[
            f"{leaf['path']}::shard{se['sid']}"]
        raw = _read_shard_range(store, mleaf, int(se["hid"]), 0,
                                int(mleaf["n_chunks"]), dt, stats)
        local = np.frombuffer(raw[:int(mleaf["nbytes"])], dtype=dt) \
            .reshape([hi - lo for lo, hi in se["bounds"]])
        out[tuple(slice(lo, hi) for lo, hi in se["bounds"])] = local
    return out


def _resharded_leaf(store, resolved: dict, leaf: dict, sharding,
                    stats: Optional[dict]):
    """One v4 leaf as a jax.Array under ``sharding``: each target shard
    assembles from only the recorded chunks its index box overlaps."""
    import jax
    dt = np_dtype(leaf["dtype"])
    shape = tuple(leaf["shape"])
    chunk_words = int(resolved["chunk_words"])
    members = resolved["members_resolved"]

    def cb(index):
        tbox = [[int(s.start or 0),
                 int(s.stop if s.stop is not None else d)]
                for s, d in zip(index, shape)]
        out = np.empty([hi - lo for lo, hi in tbox], dtype=dt)
        for se in leaf["shards"]:
            ov = box_intersect(se["bounds"], tbox)
            if ov is None:
                continue
            mleaf = _member_leaves(members[int(se["hid"])])[
                f"{leaf['path']}::shard{se['sid']}"]
            piece = _read_box(store, mleaf, int(se["hid"]), se["bounds"],
                              ov, dt, chunk_words, stats)
            out[tuple(slice(l - tl, h - tl)
                      for (l, h), (tl, _) in zip(ov, tbox))] = piece
        return out

    return jax.make_array_from_callback(shape, sharding, cb)


def _target_sharding(x):
    """``x``'s NamedSharding if it has one (the selective-restore trigger);
    None for host arrays / single-device jax arrays."""
    from jax.sharding import NamedSharding
    sh = getattr(x, "sharding", None)
    return sh if isinstance(sh, NamedSharding) else None


def stitch_tree(store, resolved: dict, like: Any = None,
                stats_out: Optional[dict] = None):
    """get_tree for a v4 sharded manifest. A `like` leaf under a
    NamedSharding restores selectively to a sharded jax.Array (reads only
    the chunks the target layout needs); other leaves stitch to full numpy
    arrays. ``stats_out`` receives {chunks_read, bytes_by_shard}."""
    stats: dict = {"chunks_read": 0, "bytes_by_shard": {}}
    like_flat = treedef = None
    if like is not None:
        import jax
        like_flat, treedef = jax.tree_util.tree_flatten(like)
        assert len(like_flat) == len(resolved["leaves"]), \
            f"structure mismatch: {len(like_flat)} vs " \
            f"{len(resolved['leaves'])}"
    arrays = []
    for i, leaf in enumerate(resolved["leaves"]):
        sharding = _target_sharding(like_flat[i]) \
            if like_flat is not None else None
        if sharding is not None:
            arrays.append(_resharded_leaf(store, resolved, leaf, sharding,
                                          stats))
        else:
            arrays.append(_stitch_leaf_full(store, resolved, leaf, stats))
    if stats_out is not None:
        stats_out.update(stats)
    if like is not None:
        import jax
        return jax.tree_util.tree_unflatten(treedef, arrays)
    return {leaf["path"]: a
            for leaf, a in zip(resolved["leaves"], arrays)}


def restore_sharded_tree(store, key: str, mesh,
                         stats_out: Optional[dict] = None) -> dict:
    """Restore a v4 checkpoint RESHARDED onto ``mesh``: each leaf's
    recorded physical spec re-resolves through
    ``parallel.sharding.respec`` (same divisibility / used-axis fallbacks
    as record-time resolution) and assembles selectively. Returns
    {path: jax.Array} — the explicit cross-mesh entry point; implicit
    resharding happens whenever ``get_tree`` receives a sharded `like`."""
    from jax.sharding import NamedSharding
    from repro.parallel.sharding import respec
    resolved = store.resolve_manifest(key)
    if resolved.get("kind") != "sharded":
        raise ValueError(f"{key!r} is not a sharded (v4) manifest")
    stats: dict = {"chunks_read": 0, "bytes_by_shard": {}}
    out = {}
    for leaf in resolved["leaves"]:
        sharding = NamedSharding(
            mesh, respec(leaf.get("spec"), leaf["shape"], mesh))
        out[leaf["path"]] = _resharded_leaf(store, resolved, leaf, sharding,
                                            stats)
    if stats_out is not None:
        stats_out.update(stats)
    return out
