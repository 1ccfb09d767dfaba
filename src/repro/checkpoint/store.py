"""Content-addressed, chunked checkpoint store (lean checkpointing substrate).

Every pytree leaf is serialized to raw bytes, split into chunks, and stored
under its blake2b hash (compressed). A checkpoint is a small manifest mapping
leaf paths to chunk-hash lists.

Dedup IS the paper's "lean checkpointing" at chunk granularity: unchanged
leaves (frozen weights in fine-tuning, optimizer slots of frozen params,
repeated epochs after convergence) share chunks with earlier checkpoints, so
the marginal bytes of a checkpoint track what actually CHANGED — without any
static analysis, because JAX state is explicit (DESIGN.md section 2).

Three manifest generations coexist:

* v1 (``put_tree``) — full manifests; every leaf lists every chunk hash.
* v2 (older pipeline manifests) — ``kind`` is ``"full"`` or ``"delta"``. A
  delta manifest names a ``parent`` key and stores only the chunk hashes
  that changed since the parent; unchanged hashes are inherited by walking
  the parent chain at read time (``resolve_manifest``). The pipeline bounds
  chain length by writing a full manifest every K checkpoints, so
  resolution never chases unbounded history.
* v3 (written by ``checkpoint/pipeline.py``) — v2 plus per-chunk ENCODINGS:
  a chunk body is either raw native bytes or a self-describing blockwise
  int8 payload (``"q8"``, kernels/ops.py wire codec). Encodings resolve
  through the parent chain exactly like hashes, and ``get_tree``
  dequantizes transparently, so readers never care which generation wrote a
  chunk.
* v4 (``kind == "sharded"``, mesh-aware pipeline) — a STITCHING manifest: a
  run recorded on a device mesh writes one ordinary v3 full/delta member
  manifest per STORE SHARD (simulated host), each covering only the device
  shards that host owns, plus a global v4 manifest recording the logical
  layout: per-leaf global shape, the recorded physical PartitionSpec, and
  each device shard's index bounds + owning store shard. Members chain
  deltas independently (``<key>.shard<h>`` -> ``<parent>.shard<h>``), so
  delta inheritance works per shard exactly as it does globally.
  ``resolve_manifest`` resolves every member chain; ``get_tree`` stitches —
  or, given a target ``NamedSharding``, reads ONLY the chunks the target
  layout overlaps and reshards (checkpoint/mesh.py), which is what lets an
  N-host recording replay bit-identically on an M-host or single-host mesh.

Multi-run sharing (run lineage). One store root may be SHARED by many runs:
each run gets a manifest namespace (``run_id``), so checkpoint keys like
``train@2.0`` never collide across runs, while the content-addressed
``objects/`` pool is shared — a fine-tune of a fine-tune stores (and, with
the warm-started pipeline, transfers) only true deltas against its ancestor
run. Cross-run references use QUALIFIED keys, ``"<run_id>::<key>"``
(``"::<key>"`` addresses the flat, un-namespaced layout explicitly — an
UNqualified key always binds to the handle's own namespace): a delta
manifest whose ``parent`` is qualified resolves through the parent run's
namespace transparently; unqualified parents resolve in the namespace of the
manifest that names them. Run records themselves (parent run, final keys,
status) live in ``checkpoint/lineage.py``'s ``RunRegistry`` beside the store.

``gc(live_keys)`` removes manifests outside the parent-closure of the live
set — ACROSS namespaces: a chunk survives while reachable from any live
manifest's chain, so deleting one run's registration reclaims only what no
surviving run inherits. Chunk writes are tmp+rename atomic: chunks are
cross-run shared state, and a truncated chunk from a killed writer must
never be silently inherited by a descendant run.

Chunks are written into pack files (``checkpoint/packs.py``), a few per
writer job, not one file per chunk: inside ``packing()`` a thread's new
chunks go into one open pack per pool, published (renamed into place) at
rollover, by ``put_manifest`` before the manifest that names them, and when
the block ends. Loose ``<h>.zst`` files of older stores stay readable and
collectable.
"""
from __future__ import annotations

import contextlib
import hashlib
import json
import os
import threading
import time
from typing import Any, Iterable, Optional

import numpy as np

from repro.checkpoint import packs
from repro.utils.codec import Compressor, pack_obj, unpack_obj

CHUNK = 4 * 1024 * 1024

MANIFEST_VERSION = 3

# what a ``CheckpointStore.counting`` block adds up: seconds, and files
WRITE_COUNTERS = {"hash_s": 0.0, "compress_s": 0.0, "file_s": 0.0,
                  "files_written": 0}

_CURRENT_RUN = object()          # sentinel: list_keys() default namespace


def _leaf_to_np(x) -> np.ndarray:
    # jax.Array -> np via __array__; np passes through
    return np.asarray(x)


def _hash(b: bytes) -> str:
    return hashlib.blake2b(b, digest_size=16).hexdigest()


def np_dtype(name: str) -> np.dtype:
    """np.dtype from a manifest dtype string, including ml_dtypes names
    (``bfloat16`` etc.) that plain numpy does not understand."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


class CheckpointStore:
    """Thread-safe on-disk store, shareable across runs. Layout:
       <root>/objects/packs/<id>.pack        — chunk payloads (shared pool),
                                               many per pack (packs.py)
       <root>/objects/<h[:2]>/<h>.zst        — loose chunks of older stores
       <root>/shards/<host>/objects/...      — per-store-shard pools (mesh
                                               record: each simulated host's
                                               local disk; same layout)
       <root>/manifests/<key>.msgpack        — un-namespaced manifests
       <root>/manifests/<run>/<key>.msgpack  — per-run manifest namespaces
       <root>/meta/[<run>/]<name>.json       — run-level metadata
       <root>/runs/<run>.json                — RunRegistry records (lineage.py)
    (File extensions are historical; the actual codec is sniffed from
    content, see utils/codec.py.)

    ``run_id`` selects the namespace unqualified keys read and write;
    ``None`` (the default, and the only mode before multi-run sharing) is
    the flat un-namespaced layout. Keys of the form ``"<run>::<key>"`` are
    fully qualified and address any namespace from any handle.
    """

    def __init__(self, root: str, compress_level: int = 3,
                 run_id: Optional[str] = None,
                 prefer_shards: Optional[Iterable] = None):
        self.root = root
        self.run_id = run_id
        # shard-pool read affinity: a multi-host replay worker that only has
        # its own host's pool mounted locally lists those shard ids here, so
        # fallback chunk scans hit local disk first. Purely an ORDERING —
        # content addressing keeps every pool a valid source, so resharded
        # restores that need another host's chunks still work when the
        # store root is shared (network FS).
        self.prefer_shards = [str(s) for s in (prefer_shards or ())]
        os.makedirs(os.path.join(root, "objects"), exist_ok=True)
        os.makedirs(os.path.join(root, "manifests"), exist_ok=True)
        os.makedirs(os.path.join(root, "meta"), exist_ok=True)
        self._codec = Compressor(level=compress_level)
        self._lock = threading.RLock()
        # per thread: the counters dict of an open ``counting`` block, and
        # the open packs of a ``packing`` block
        self._tl = threading.local()
        # what this handle knows of each chunk pool, by shard id (None: flat)
        self._pools: dict[Optional[str], _Pool] = {}
        # objects/<h[:2]>/ (and manifest-namespace) fan-out dirs, cached to
        # avoid a mkdir syscall on every chunk (the delta pipeline writes
        # many small chunks)
        self._dirs: set[str] = set()

    # ------------------------------------------------------------ naming --
    def _split_key(self, key: str) -> tuple[Optional[str], str]:
        """(run namespace, run-local key). Unqualified keys belong to this
        handle's namespace."""
        if "::" in key:
            rid, k = key.split("::", 1)
            return rid or None, k
        return self.run_id, key

    def _norm_key(self, key: str) -> tuple[Optional[str], str]:
        """Filesystem-space identity: (sanitized namespace | None, sanitized
        key). Idempotent for already-sanitized names, so raw keys
        ('train@2.0') and list_keys() output ('train_at_2.0') normalize to
        the same tuple."""
        rid, k = self._split_key(key)
        return (_safe(rid) if rid else None, _safe(k))

    def qualify(self, key: str) -> str:
        """This handle's fully-qualified form of a run-local key."""
        if self.run_id and "::" not in key:
            return f"{self.run_id}::{key}"
        return key

    def _ensure_dir(self, d: str):
        if d not in self._dirs:
            os.makedirs(d, exist_ok=True)
            self._dirs.add(d)

    # ------------------------------------------------------------ chunks --
    def _pool_dir(self, shard=None) -> str:
        """A chunk pool: the flat shared pool, or (``shard``) one store
        shard's pool — ``shards/<h(ost)>/objects/`` — which in a real
        deployment is that host's local disk."""
        if shard is None:
            return os.path.join(self.root, "objects")
        return os.path.join(self.root, "shards", str(shard), "objects")

    def _chunk_path(self, h: str, shard=None) -> str:
        """Path of a loose chunk file (stores written before packs)."""
        return os.path.join(self._pool_dir(shard), h[:2], h + ".zst")

    def _shard_ids(self) -> list[str]:
        """Store shards with a chunk pool on disk (sorted numerically when
        possible so fallback scans are deterministic)."""
        d = os.path.join(self.root, "shards")
        if not os.path.isdir(d):
            return []
        ids = [e for e in os.listdir(d)
               if os.path.isdir(os.path.join(d, e))]
        return sorted(ids, key=lambda s: (not s.isdigit(),
                                          int(s) if s.isdigit() else s))

    def _pool(self, shard=None) -> "_Pool":
        key = None if shard is None else str(shard)
        pool = self._pools.get(key)
        if pool is None:
            with self._lock:
                pool = self._pools.setdefault(key,
                                              _Pool(self._pool_dir(key)))
        return pool

    def _scan(self, pool: "_Pool"):
        with self._lock:
            pool.scan()

    def _all_pools(self) -> list["_Pool"]:
        return [self._pool(s) for s in [None] + self._shard_ids()]

    def _pool_order(self, shard=None) -> list:
        """Pools to look in for a chunk: ``shard``'s, the preferred shards',
        the flat pool, then every other shard pool. Content addressing makes
        any copy valid; the fallback keeps reads working when a tree is
        restored on a different mesh shape than recorded it."""
        order = [] if shard is None else [str(shard)]
        for s in self.prefer_shards + [None] + self._shard_ids():
            if s not in order:
                order.append(s)
        return order

    def _find_chunk(self, h: str, shard=None) -> Optional[str]:
        """Locate a loose chunk file, in ``_pool_order`` (``_locate``'s
        fallback, once it has scanned every pool)."""
        for s in self._pool_order(shard):
            p = self._chunk_path(h, s)
            if self._pool(s).loose and os.path.exists(p):
                return p
        return None

    def _locate(self, h: str, shard=None) -> Optional[tuple]:
        """Where chunk ``h`` lives, as (path, offset, length): a record of
        a published pack, or a loose file (offset 0, length None); None if
        nowhere. Pools are tried in ``_pool_order``. On a miss the packs
        published since this handle last looked (by other handles,
        processes or runs sharing the root) are read, then loose files."""
        first = self._pool(shard)
        if not first.scanned:
            self._scan(first)
        loc = first.where.get(h)
        if loc is not None:
            return loc
        order = [self._pool(s) for s in self._pool_order(shard)]
        for rescan in (False, True):
            for pool in order:
                if rescan or not pool.scanned:
                    self._scan(pool)
                loc = pool.where.get(h)
                if loc is not None:
                    return loc
        p = self._find_chunk(h, shard)
        return None if p is None else (p, 0, None)

    def _stored_in(self, pool: "_Pool", h: str, shard) -> bool:
        """Whether ``h`` is already durable in ``pool`` (the dedup check).
        Another handle's gc may have rewritten or removed the pack this
        handle knows it from, so a hit is checked on disk, and the pool
        read again if the pack is gone."""
        if not pool.scanned:
            self._scan(pool)
        loc = pool.where.get(h)
        if loc is not None:
            if os.path.exists(loc[0]):
                return True
            self._scan(pool)
            if h in pool.where:
                return True
        return pool.loose and os.path.exists(self._chunk_path(h, shard))

    def _chunk_cost(self, h: str) -> int:
        """On-disk bytes chunk ``h`` accounts for (``packs.record_cost``,
        or a loose file's size); 0 if it is nowhere."""
        loc = self._locate(h)
        if loc is None:
            return 0
        path, off, n = loc
        if n is not None:
            return packs.record_cost(off, n)
        try:
            return os.path.getsize(path)
        except OSError:
            return 0

    @contextlib.contextmanager
    def counting(self, counters: dict):
        """Within the block, this thread's ``put_chunk`` and
        ``put_manifest`` add into ``counters`` (the keys of
        ``WRITE_COUNTERS``, started at 0): ``hash_s`` blake2b, ``compress_s``
        the codec, ``file_s`` the dedup check, pack writes and publication,
        and the manifest write; ``files_written`` the packs and manifests
        published."""
        for k, zero in WRITE_COUNTERS.items():
            counters.setdefault(k, zero)
        outer = getattr(self._tl, "counters", None)
        self._tl.counters = counters
        try:
            yield counters
        finally:
            self._tl.counters = outer

    def _tally(self, hash_s: float, compress_s: float, file_s: float):
        c = getattr(self._tl, "counters", None)
        if c is not None:
            c["hash_s"] += hash_s
            c["compress_s"] += compress_s
            c["file_s"] += file_s

    def _count_file(self):
        c = getattr(self._tl, "counters", None)
        if c is not None:
            c["files_written"] += 1

    @contextlib.contextmanager
    def packing(self):
        """Within the block, this thread's new chunks go into one open pack
        per pool instead of a pack each. ``put_manifest`` publishes them
        before the manifest that may name their chunks, as do a pack's
        passing ``packs.PACK_BYTES`` and the block's end. A nested block
        joins the outer one."""
        if getattr(self._tl, "packs", None) is not None:
            yield
            return
        self._tl.packs = {}
        try:
            yield
        finally:
            t0 = time.perf_counter()
            try:
                self._publish_open()
            finally:
                self._tl.packs = None
                self._tally(0.0, 0.0, time.perf_counter() - t0)

    def _publish(self, pool: "_Pool", w: packs.PackWriter) -> int:
        """Publish one open pack and index it; returns its size."""
        try:
            size = w.publish()
        except BaseException:
            w.abort()
            raise
        with self._lock:
            pool.add(w.path, w.entries)
        self._count_file()
        return size

    def _publish_open(self):
        """Publish this thread's open packs, if it is in a ``packing``
        block. Should one fail, the others are dropped too."""
        open_packs = getattr(self._tl, "packs", None)
        try:
            while open_packs:
                self._publish(*open_packs.popitem())
        finally:
            for w in (open_packs or {}).values():
                w.abort()
            if open_packs:
                open_packs.clear()

    def put_chunk(self, data: bytes, shard=None) -> tuple[str, int, bool]:
        """Store one content-addressed chunk (``shard`` selects a store
        shard's pool — bytes recorded on a host land on that host's disk).
        Returns (hash, bytes_written, was_new): ``bytes_written`` is the
        chunk's ``packs.record_cost``, so over a job it sums to the size of
        the packs the job published. Inside ``packing()`` the chunk joins
        this thread's open pack for the pool; outside, it is published at
        once in a pack of its own."""
        t0 = time.perf_counter()
        h = _hash(data)
        t1 = time.perf_counter()
        pool = self._pool(shard)
        open_packs = getattr(self._tl, "packs", None)
        w = None if open_packs is None else open_packs.get(pool)
        if (w is not None and h in w.entries) \
                or self._stored_in(pool, h, shard):
            self._tally(t1 - t0, 0.0, time.perf_counter() - t1)
            return h, 0, False
        t2 = time.perf_counter()
        payload = self._codec.compress(data)
        t3 = time.perf_counter()
        if w is None:
            self._ensure_dir(pool.packs_dir)
            w = packs.PackWriter(pool.packs_dir)
            if open_packs is not None:
                open_packs[pool] = w
        try:
            nb = w.add(h, payload)
        except BaseException:
            # the pack's file no longer matches its entries: drop it
            if open_packs is not None:
                open_packs.pop(pool, None)
            w.abort()
            raise
        if open_packs is None or w.size >= packs.PACK_BYTES:
            if open_packs is not None:
                del open_packs[pool]
            self._publish(pool, w)
        self._tally(t1 - t0, t3 - t2, t2 - t1 + time.perf_counter() - t3)
        return h, nb, True

    # kept under the old private name too — tests and older callers use it
    _put_chunk = put_chunk

    def get_chunk(self, h: str, shard=None) -> bytes:
        for _ in range(2):
            loc = self._locate(h, shard)
            if loc is None:
                break
            path, off, n = loc
            try:
                if n is None:
                    with open(path, "rb") as f:
                        payload = f.read()
                else:
                    payload = packs.read_record(path, off, n)
            except FileNotFoundError:
                # another handle's gc rewrote or removed the pack
                for pool in list(self._pools.values()):
                    if path in pool.packs:
                        self._scan(pool)
                continue
            return self._codec.decompress(payload)
        raise FileNotFoundError(f"chunk {h} not in any pool of {self.root}")

    _get_chunk = get_chunk

    def _iter_chunk_files(self):
        """Every file across the flat pool and all shard pools as (pool,
        path, filename) — the single sweep gc/stats share."""
        for pool in self._all_pools():
            for dirpath, _, files in os.walk(pool.path):
                for fn in files:
                    yield pool, os.path.join(dirpath, fn), fn

    def _pack_index(self, pool: "_Pool", path: str) -> Optional[dict]:
        """A published pack's index, read once per handle; None if it is
        gone or not a whole pack."""
        idx = pool.packs.get(path)
        if idx is None:
            try:
                idx = packs.read_index(path)
            except (FileNotFoundError, ValueError):
                return None
            with self._lock:
                pool.add(path, idx)
        return idx

    def _rewrite_pack(self, pool: "_Pool", path: str, live: dict) -> int:
        """Replace a pack by one holding only its ``live`` records ({hash:
        (offset, length)}), or remove it if none are. The new pack is
        published before the old one goes, so every live chunk stays
        readable throughout. Returns the bytes freed."""
        freed = os.path.getsize(path)
        if live:
            w = packs.PackWriter(pool.packs_dir)
            try:
                fd = os.open(path, os.O_RDONLY)
                try:
                    for h, (off, n) in sorted(live.items(),
                                              key=lambda e: e[1][0]):
                        w.add(h, os.pread(fd, n, off))
                finally:
                    os.close(fd)
            except BaseException:
                w.abort()
                raise
            freed -= self._publish(pool, w)
        os.remove(path)
        with self._lock:
            pool.forget(path)
        return freed

    # --------------------------------------------------------- manifests --
    def _mpath(self, rid_safe: Optional[str], key_safe: str) -> str:
        parts = [self.root, "manifests"]
        if rid_safe:
            parts.append(rid_safe)
        parts.append(key_safe + ".msgpack")
        return os.path.join(*parts)

    def _manifest_path(self, key: str) -> str:
        return self._mpath(*self._norm_key(key))

    def put_manifest(self, manifest: dict, key: Optional[str] = None):
        """Atomically persist a manifest (crash-safe tmp+rename), after
        publishing this thread's open packs, so a manifest never names a
        chunk that is not on disk yet. ``key`` defaults to the manifest's
        own (run-local) key."""
        t0 = time.perf_counter()
        self._publish_open()
        mpath = self._manifest_path(key if key is not None
                                    else manifest["key"])
        self._ensure_dir(os.path.dirname(mpath))
        _atomic_write(mpath, pack_obj(manifest))
        self._count_file()
        self._tally(0.0, 0.0, time.perf_counter() - t0)

    def get_manifest(self, key: str) -> dict:
        with open(self._manifest_path(key), "rb") as f:
            return unpack_obj(f.read())

    def delete_manifest(self, key: str, delete_chunks: bool = False):
        """Remove one manifest; optionally its directly-listed chunks.
        ``delete_chunks`` is only safe when the caller knows the chunks are
        not shared (e.g. the unique random calibration probe)."""
        if delete_chunks:
            try:
                m = self.get_manifest(key)
            except FileNotFoundError:
                m = None
            if m is not None:
                self._drop_chunks(set(_manifest_chunk_hashes(m)))
        try:
            os.remove(self._manifest_path(key))
        except FileNotFoundError:
            pass

    def _drop_chunks(self, hashes: set):
        """Remove chunks from the flat pool: their loose files, and their
        records from the packs that hold them."""
        with self._lock:
            pool = self._pool()
            self._scan(pool)
            if pool.loose:
                for h in hashes:
                    try:
                        os.remove(self._chunk_path(h))
                    except FileNotFoundError:
                        pass
            for path, idx in list(pool.packs.items()):
                if not hashes.isdisjoint(idx):
                    self._rewrite_pack(pool, path,
                                       {h: loc for h, loc in idx.items()
                                        if h not in hashes})

    def _load_tuple(self, t: tuple, cache: dict) -> Optional[dict]:
        """Memoized manifest read by normalized (rid, key) tuple; None for a
        missing file. Shared by stats() and gc() so each manifest is read at
        most once per pass."""
        if t not in cache:
            try:
                with open(self._mpath(*t), "rb") as f:
                    cache[t] = unpack_obj(f.read())
            except FileNotFoundError:
                cache[t] = None
        return cache[t]

    def _parent_of(self, manifest: dict,
                   child_rid_safe: Optional[str]) -> Optional[tuple]:
        """Normalized (rid, key) of a manifest's parent. Unqualified parents
        live in the same namespace as the child manifest."""
        parent = manifest.get("parent")
        if not parent:
            return None
        if "::" in parent:
            rid, k = parent.split("::", 1)
            return (_safe(rid) if rid else None, _safe(k))
        return (child_rid_safe, _safe(parent))

    def resolve_manifest(self, key: str, _max_depth: int = 10_000) -> dict:
        """Return a manifest with every leaf's full chunk-hash list, walking
        the delta parent chain as needed — across run namespaces when the
        chain crosses a run boundary (warm-started derived runs). v1 and
        full v2 manifests return (normalized) as-is."""
        cur_rid, _ = self._split_key(key)
        manifest = self.get_manifest(key)
        if manifest.get("kind") == "sharded":
            # v4 stitching manifest: resolve every member chain. Members are
            # plain v3 full/delta manifests (one per store shard) living in
            # the SAME namespace as the global key, so each member chain
            # inherits deltas independently, across run lineage included.
            resolved = dict(manifest)
            members: dict[int, dict] = {}
            hops = 0
            for hid, mkey in (manifest.get("members") or {}).items():
                mres = self.resolve_manifest(f"{cur_rid or ''}::{mkey}",
                                             _max_depth=_max_depth)
                members[int(hid)] = mres
                hops = max(hops, int(mres.get("hops", 0)))
            resolved["members_resolved"] = members
            # a restore pays the DEEPEST member chain (shards resolve in
            # parallel on their owning hosts)
            resolved["hops"] = hops
            return resolved
        if manifest.get("version", 1) < 2 or manifest.get("kind", "full") == "full":
            return manifest
        # delta: seed hole-filled lists from this manifest, then walk
        # parents. Per-chunk encodings (v3) resolve alongside the hashes: an
        # enc slot is filled from whichever manifest supplied the chunk.
        leaves = []
        unresolved: dict[str, dict] = {}
        for leaf in manifest["leaves"]:
            n = int(leaf["n_chunks"])
            if leaf.get("chunks"):
                # already-complete list (e.g. a re-saved resolved manifest)
                chunks = list(leaf["chunks"])
                enc = list(leaf.get("enc") or ["raw"] * n)
            else:
                chunks = [None] * n
                enc = [None] * n
                denc = leaf.get("denc") or {}
                for i, h in (leaf.get("delta") or {}).items():
                    chunks[int(i)] = h
                    enc[int(i)] = denc.get(i, "raw")
            out = dict(leaf)
            out.pop("delta", None)
            out.pop("denc", None)
            out["chunks"] = chunks
            out["_enc"] = enc
            leaves.append(out)
            if any(c is None for c in chunks):
                unresolved[leaf["path"]] = out
        parent = manifest.get("parent")
        depth = 0
        while unresolved and parent is not None:
            depth += 1
            if depth > _max_depth:
                raise RuntimeError(f"delta chain too deep resolving {key!r}")
            if "::" in parent:
                cur_rid, parent = parent.split("::", 1)
                cur_rid = cur_rid or None
            # always re-qualify: "::key" is the explicit flat form — a bare
            # key would rebind to THIS handle's namespace
            pkey = f"{cur_rid or ''}::{parent}"
            try:
                pm = self.get_manifest(pkey)
            except FileNotFoundError as e:
                raise RuntimeError(
                    f"delta manifest {key!r} references missing parent "
                    f"{pkey!r} — deleted outside store.gc (which retains "
                    f"the parent closure across run lineage)?") from e
            by_path = {lf["path"]: lf for lf in pm["leaves"]}
            for path, out in list(unresolved.items()):
                src = by_path.get(path)
                if src is None:
                    continue
                if "chunks" in src and src["chunks"] is not None:
                    senc = src.get("enc")
                    for i, c in enumerate(out["chunks"]):
                        if c is None:
                            out["chunks"][i] = src["chunks"][i]
                            out["_enc"][i] = senc[i] if senc else "raw"
                else:
                    sdenc = src.get("denc") or {}
                    for i_s, h in (src.get("delta") or {}).items():
                        i = int(i_s)
                        if out["chunks"][i] is None:
                            out["chunks"][i] = h
                            out["_enc"][i] = sdenc.get(i_s, "raw")
                if all(c is not None for c in out["chunks"]):
                    del unresolved[path]
            parent = pm.get("parent") \
                if pm.get("version", 1) >= 2 and pm.get("kind") == "delta" \
                else None
        if unresolved:
            missing = {p: [i for i, c in enumerate(o["chunks"]) if c is None]
                       for p, o in unresolved.items()}
            raise RuntimeError(
                f"unresolvable delta manifest {key!r}: missing chunks "
                f"{missing} (parent chain broken — was the store gc'd with "
                f"an incomplete live set?)")
        for out in leaves:
            enc = ["raw" if e is None else e for e in out.pop("_enc")]
            if any(e != "raw" for e in enc):
                out["enc"] = enc
            else:
                out.pop("enc", None)
        resolved = dict(manifest)
        resolved["leaves"] = leaves
        # parent hops this resolution actually walked — restore accounting
        # feeds it to the learned cost model (calibration meta "hop_s")
        resolved["hops"] = depth
        return resolved

    # ------------------------------------------------------------- trees --
    def put_tree(self, key: str, tree: Any, meta: Optional[dict] = None) -> dict:
        """Serialize a pytree of arrays as a v1 full manifest.
        Returns stats incl. dedup savings. (The delta-aware record path lives
        in checkpoint/pipeline.py; this remains the simple whole-tree API.)"""
        import jax
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        leaves = []
        new_bytes = 0
        total_bytes = 0
        new_chunks = 0
        total_chunks = 0
        with self.packing():
            for path, leaf in flat:
                arr = _leaf_to_np(leaf)
                raw = arr.tobytes()
                chunks = []
                for off in range(0, max(len(raw), 1), CHUNK):
                    piece = raw[off:off + CHUNK]
                    h, nb, new = self.put_chunk(piece)
                    chunks.append(h)
                    new_bytes += nb
                    total_bytes += len(piece)
                    new_chunks += int(new)
                    total_chunks += 1
                leaves.append({
                    "path": jax.tree_util.keystr(path),
                    "dtype": str(arr.dtype),
                    "shape": list(arr.shape),
                    "chunks": chunks,
                })
            manifest = {
                "key": self._split_key(key)[1],
                "treedef": str(treedef),
                "leaves": leaves,
                "meta": meta or {},
            }
            self.put_manifest(manifest, key=key)
        return {"key": key, "total_bytes": total_bytes, "new_bytes": new_bytes,
                "total_chunks": total_chunks, "new_chunks": new_chunks}

    def get_tree(self, key: str, like: Any = None,
                 manifest: Optional[dict] = None,
                 stats_out: Optional[dict] = None):
        """Load a checkpoint (delta manifests resolve transparently, across
        run lineage). If `like` (a pytree with the same structure) is given,
        arrays are unflattened into that structure; otherwise a flat
        {path: array} dict is returned. Pass a pre-``resolve_manifest``'d
        `manifest` to skip re-resolution (warm-start reads it anyway).
        Returned arrays are WRITABLE copies — np.frombuffer views are
        read-only and silently break in-place consumers.

        v4 sharded manifests stitch through checkpoint/mesh.py: a `like`
        leaf carrying a ``NamedSharding`` restores SELECTIVELY (only the
        chunks its target shards overlap are read) and comes back as a
        sharded ``jax.Array``; other leaves stitch to full numpy arrays.
        ``stats_out`` (a dict, sharded path only) receives read accounting:
        {chunks_read, bytes_by_shard}."""
        import jax
        if manifest is None:
            manifest = self.resolve_manifest(key)
        if manifest.get("kind") == "sharded":
            from repro.checkpoint.mesh import stitch_tree
            return stitch_tree(self, manifest, like=like,
                               stats_out=stats_out)
        arrays = []
        for leaf in manifest["leaves"]:
            dt = np_dtype(leaf["dtype"])
            enc = leaf.get("enc")
            if enc and any(e != "raw" for e in enc):
                # encoded chunks decode transparently to native bytes — q8,
                # q4, and entropy-compressed ("+z") payloads alike (deferred
                # import: the wire codecs live with the kernels, and the
                # store stays importable without pulling jax)
                from repro.kernels.ops import decode_wire_chunk
                raw = b"".join(
                    decode_wire_chunk(self.get_chunk(h), e, dt)
                    for h, e in zip(leaf["chunks"], enc))
            else:
                raw = b"".join(self.get_chunk(h) for h in leaf["chunks"])
            nbytes = int(leaf.get("nbytes",
                                  int(np.prod(leaf["shape"], dtype=np.int64))
                                  * dt.itemsize))
            arr = np.frombuffer(raw[:nbytes], dtype=dt).copy()
            arrays.append(arr.reshape(leaf["shape"]))
        if like is not None:
            flat, treedef = jax.tree_util.tree_flatten(like)
            assert len(flat) == len(arrays), \
                f"structure mismatch: {len(flat)} vs {len(arrays)}"
            return jax.tree_util.tree_unflatten(treedef, arrays)
        return {leaf["path"]: a for leaf, a in zip(manifest["leaves"], arrays)}

    def has(self, key: str) -> bool:
        return os.path.exists(self._manifest_path(key))

    def list_keys(self, run=_CURRENT_RUN) -> list[str]:
        """Sanitized run-local manifest names in one namespace (default:
        this handle's)."""
        rid = self.run_id if run is _CURRENT_RUN else run
        d = os.path.join(self.root, "manifests")
        if rid:
            d = os.path.join(d, _safe(rid))
        if not os.path.isdir(d):
            return []
        return sorted(f[: -len(".msgpack")] for f in os.listdir(d)
                      if f.endswith(".msgpack")
                      and not os.path.isdir(os.path.join(d, f)))

    def list_namespaces(self) -> list[str]:
        """Sanitized run namespaces that have at least one manifest dir."""
        d = os.path.join(self.root, "manifests")
        return sorted(e for e in os.listdir(d)
                      if os.path.isdir(os.path.join(d, e)))

    def _iter_manifest_tuples(self):
        """Every manifest in the store as (rid_safe | None, key_safe)."""
        for k in self.list_keys(run=None):
            yield (None, k)
        for rid in self.list_namespaces():
            for k in self.list_keys(run=rid):
                yield (rid, k)

    # --------------------------------------------------------------- stats --
    def stats(self, keys: Optional[Iterable[str]] = None,
              include_chunks: bool = True, per_key: bool = False) -> dict:
        """Single-pass, memoized summary of manifests (default: the whole
        store; pass `keys` — possibly qualified — to restrict to one run's
        manifests while chain depths still follow parents across runs).
        Returns {manifests, full_manifests, delta_manifests, max_chain_depth,
        chunks, stored_bytes}. Chain depth is the number of parent hops a
        restore resolves; broken links (missing parents) end the chain
        rather than raising — this is a diagnostic, not a restore.
        `include_chunks=False` skips the objects-pool walk (O(store) stat
        calls on a large shared pool) and reports chunks/stored_bytes as
        0 — use it when only manifest counts/depths are needed.
        `per_key=True` adds a ``per_key`` map {input key: {depth, kind,
        direct_chunks}} — the resume-cost raw material the replay planner
        turns into per-segment estimates."""
        cache: dict[tuple, Optional[dict]] = {}

        def load(t):
            return self._load_tuple(t, cache)

        if keys is not None:
            key_list = list(keys)
            targets = [self._norm_key(k) for k in key_list]
        else:
            key_list = None
            targets = list(self._iter_manifest_tuples())
        depth: dict[tuple, int] = {}
        counts = {"full": 0, "delta": 0}
        max_depth = 0
        n_manifests = 0
        info: dict[tuple, dict] = {}

        def walk(t0) -> int:
            """Chain depth of one manifest tuple — walk up to the first
            memoized ancestor (or the chain end), then unwind; every
            manifest is read at most once store-wide."""
            chain: list[tuple] = []
            seen: set[tuple] = set()
            t = t0
            while t is not None and t not in depth and t not in seen:
                seen.add(t)
                mm = load(t)
                if mm is None:
                    depth[t] = 0          # broken link: chain ends here
                    break
                chain.append(t)
                t = self._parent_of(mm, t[0])
            for node in reversed(chain):
                p = self._parent_of(load(node), node[0])
                depth[node] = depth[p] + 1 if p is not None and p in depth \
                    else (1 if p is not None and p in seen else 0)
            return depth.get(t0, 0)

        for t0 in targets:
            m = load(t0)
            if m is None:
                continue
            n_manifests += 1
            kind = m.get("kind", "full") if m.get("version", 1) >= 2 else "full"
            counts[kind] = counts.get(kind, 0) + 1
            d0 = walk(t0)
            shards_info = None
            if kind == "sharded":
                # v4: depth/chunks live on the per-store-shard member
                # chains; a restore pays the deepest one (shards resolve in
                # parallel), so that is the depth reported for the key
                shards_info = {}
                for hid, mkey in (m.get("members") or {}).items():
                    mt = (t0[0], _safe(mkey))
                    mm = load(mt)
                    if mm is None:
                        continue
                    shards_info[str(hid)] = {
                        "depth": walk(mt),
                        "chunks": sum(1 for _ in _manifest_chunk_hashes(mm)),
                    }
                if shards_info:
                    d0 = max(s["depth"] for s in shards_info.values())
            max_depth = max(max_depth, d0)
            if per_key:
                direct = sum(1 for _ in _manifest_chunk_hashes(m))
                encc = _manifest_enc_counts(m)
                if shards_info:
                    direct = sum(s["chunks"] for s in shards_info.values())
                    encc = {}
                    for hid, mkey in (m.get("members") or {}).items():
                        mm = load((t0[0], _safe(mkey)))
                        if mm is None:
                            continue
                        for e, c in _manifest_enc_counts(mm).items():
                            encc[e] = encc.get(e, 0) + c
                info[t0] = {"depth": d0, "kind": kind,
                            "direct_chunks": direct,
                            "enc_counts": encc}
                if shards_info is not None:
                    info[t0]["shards"] = shards_info
        chunks = 0
        stored = 0
        if include_chunks:
            for pool, p, fn in self._iter_chunk_files():
                if fn.endswith(".zst"):
                    chunks += 1
                    stored += os.path.getsize(p)
                elif fn.endswith(packs.SUFFIX):
                    idx = self._pack_index(pool, p)
                    if idx is not None:
                        chunks += len(idx)
                        stored += os.path.getsize(p)
        out = {"manifests": n_manifests,
               "full_manifests": counts.get("full", 0),
               "delta_manifests": counts.get("delta", 0),
               "sharded_manifests": counts.get("sharded", 0),
               "max_chain_depth": max_depth,
               "chunks": chunks, "stored_bytes": stored}
        if per_key:
            if key_list is not None:
                out["per_key"] = {k: info[self._norm_key(k)]
                                  for k in key_list
                                  if self._norm_key(k) in info}
            else:
                # whole-store pass: qualified "rid::key" form ("::key" =
                # explicit flat namespace)
                out["per_key"] = {f"{rid or ''}::{k}": v
                                  for (rid, k), v in info.items()}
        return out

    def encoding_mix(self, key: str) -> dict:
        """Resolved per-encoding storage mix of one checkpoint: for every
        chunk a restore of `key` reads (chain-inherited included),
        {enc: {"chunks": n, "stored_bytes": b}} with b the on-disk
        (compressed) size — dedup-shared chunks count once per reference,
        matching what a restore actually reads. v4 sharded keys aggregate
        over all member manifests."""
        m = self.resolve_manifest(key)
        mix: dict[str, dict] = {}
        size_cache: dict[str, int] = {}

        def chunk_size(h: str) -> int:
            if h not in size_cache:
                size_cache[h] = self._chunk_cost(h)
            return size_cache[h]

        def add_leaves(leaves):
            for leaf in leaves:
                enc = leaf.get("enc")
                for i, h in enumerate(leaf.get("chunks") or []):
                    if h is None:
                        continue
                    e = enc[i] if enc else "raw"
                    d = mix.setdefault(e, {"chunks": 0, "stored_bytes": 0})
                    d["chunks"] += 1
                    d["stored_bytes"] += chunk_size(h)

        if m.get("kind") == "sharded":
            for mm in (m.get("members_resolved") or {}).values():
                add_leaves(mm["leaves"])
        else:
            add_leaves(m["leaves"])
        return mix

    # ------------------------------------------------------------ closure --
    def _parent_closure(self, keys: Iterable[str],
                        cache: dict) -> set[tuple]:
        """Normalized (rid, key) tuples of `keys` plus every ancestor their
        delta chains resolve through (across run namespaces) AND, for v4
        sharded manifests, their per-store-shard member manifests — a live
        stitching manifest pins every shard chain it stitches, so multi-run
        gc can never collect a live shard's chunks. Tuples whose manifest is
        missing are dropped."""
        live = {self._norm_key(k) for k in keys}
        frontier = list(live)
        while frontier:
            t = frontier.pop()
            m = self._load_tuple(t, cache)
            if m is None:
                live.discard(t)
                continue
            nxt = []
            p = self._parent_of(m, t[0])
            if p is not None:
                nxt.append(p)
            # sharded (v4) members live in the global key's namespace
            for mkey in (m.get("members") or {}).values():
                nxt.append((t[0], _safe(mkey)))
            for p in nxt:
                if p not in live:
                    live.add(p)
                    frontier.append(p)
        return live

    def closure_chunks(self, keys: Iterable[str]) -> set[str]:
        """Every chunk hash reachable from `keys`' manifest parent closure —
        the byte footprint a set of checkpoints actually pins. Two runs'
        closures intersected/differenced give the `runs diff` view of what
        lineage sharing saves."""
        cache: dict[tuple, Optional[dict]] = {}
        hashes: set[str] = set()
        for t in self._parent_closure(keys, cache):
            m = self._load_tuple(t, cache)
            if m is not None:
                hashes.update(_manifest_chunk_hashes(m))
        return hashes

    def chunk_bytes(self, hashes: Iterable[str]) -> int:
        """On-disk bytes of the given chunk hashes, wherever they live
        (flat or shard pools): a pack record with its index entry (and a
        pack's first record its trailer, ``packs.record_cost``), or a loose
        file; missing chunks count 0. Over the chunks a job wrote this is
        the size of the packs it published."""
        return sum(self._chunk_cost(h) for h in hashes)

    # ---------------------------------------------------------------- gc --
    def gc(self, live_keys: Iterable[str]) -> dict:
        """Delete manifests outside the parent-closure of ``live_keys`` and
        every chunk no surviving manifest references. The closure follows
        delta parents ACROSS run namespaces (qualified ``run::key`` refs), so
        a derived run pins exactly the ancestor manifests its chain resolves
        through — a chunk survives while ANY live run can still reach it.
        Returns {kept_manifests, deleted_manifests, kept_chunks,
        deleted_chunks, deleted_bytes}."""
        with self._lock:
            cache: dict[tuple, Optional[dict]] = {}

            def load(t):
                return self._load_tuple(t, cache)

            # normalize to filesystem-space (rid, key) tuples (callers pass
            # raw keys, listings yield sanitized names) and take the parent
            # closure: a live delta manifest pins its ancestry, run
            # boundaries included
            live = self._parent_closure(live_keys, cache)
            referenced: set[str] = set()
            deleted_manifests = 0
            namespaces: set[Optional[str]] = set()
            for t in list(self._iter_manifest_tuples()):
                namespaces.add(t[0])
                if t not in live:
                    try:
                        os.remove(self._mpath(*t))
                    except FileNotFoundError:
                        pass
                    deleted_manifests += 1
                    continue
                m = load(t)
                if m is not None:
                    referenced.update(_manifest_chunk_hashes(m))
            for rid in namespaces:       # drop emptied namespace dirs
                if rid:
                    try:
                        os.rmdir(os.path.join(self.root, "manifests", rid))
                    except OSError:
                        pass
            kept = deleted = deleted_bytes = deleted_tmp = 0
            now = time.time()
            # sweep the flat pool AND every store shard's pool — a chunk
            # hash is live wherever it lives. Within a pool one copy of a
            # live chunk is kept (a loose file first, then the oldest pack);
            # a pack left with no live record goes, and one that is partly
            # live is rewritten with its live records only
            kept_in: set[tuple] = set()
            pack_files = []
            for pool, p, fn in self._iter_chunk_files():
                if fn.endswith(packs.SUFFIX):
                    pack_files.append((fn, pool, p))
                elif not fn.endswith(".zst"):
                    # stray .tmp from a KILLED writer (the in-process
                    # failure path unlinks its own): reclaim once aged —
                    # a live writer holds a tmp for far less, so the age
                    # gate never races an in-flight write
                    deleted_tmp += _reclaim_stale_tmp(p, now)
                elif fn[: -len(".zst")] in referenced:
                    kept += 1
                    kept_in.add((pool.path, fn[: -len(".zst")]))
                else:
                    deleted_bytes += os.path.getsize(p)
                    os.remove(p)
                    deleted += 1
            for _, pool, p in sorted(pack_files, key=lambda e: e[0]):
                idx = self._pack_index(pool, p)
                if idx is None:
                    continue
                keep = {h: loc for h, loc in idx.items()
                        if h in referenced and (pool.path, h) not in kept_in}
                kept_in.update((pool.path, h) for h in keep)
                kept += len(keep)
                if len(keep) < len(idx):
                    deleted += len(idx) - len(keep)
                    deleted_bytes += self._rewrite_pack(pool, p, keep)
            for dirpath, _, files in os.walk(os.path.join(self.root,
                                                          "manifests")):
                for fn in files:
                    if not fn.endswith(".msgpack"):
                        deleted_tmp += _reclaim_stale_tmp(
                            os.path.join(dirpath, fn), now)
            return {"kept_manifests": len(live), "deleted_manifests": deleted_manifests,
                    "kept_chunks": kept, "deleted_chunks": deleted,
                    "deleted_bytes": deleted_bytes,
                    "deleted_tmp_files": deleted_tmp}

    # -------------------------------------------------------------- meta --
    def _meta_path(self, name: str) -> str:
        parts = [self.root, "meta"]
        if self.run_id:
            parts.append(_safe(self.run_id))
        parts.append(_safe(name) + ".json")
        return os.path.join(*parts)

    def put_meta(self, name: str, obj: dict):
        path = self._meta_path(name)
        self._ensure_dir(os.path.dirname(path))
        _atomic_write(path, json.dumps(obj, indent=1, default=str).encode())

    def get_meta(self, name: str) -> Optional[dict]:
        path = self._meta_path(name)
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    def stored_bytes(self) -> int:
        total = 0
        for _, p, _ in self._iter_chunk_files():
            total += os.path.getsize(p)
        return total

    def shard_stored_bytes(self) -> dict:
        """On-disk bytes per store shard pool — the `runs show` per-shard
        breakdown."""
        out: dict[str, int] = {}
        for s in self._shard_ids():
            total = 0
            pool = os.path.join(self.root, "shards", s, "objects")
            for dirpath, _, files in os.walk(pool):
                for fn in files:
                    total += os.path.getsize(os.path.join(dirpath, fn))
            out[s] = total
        return out


def _atomic_write(path: str, payload: bytes):
    """Crash-safe write: tmp file + atomic rename, tmp unlinked on failure.
    A killed writer can leave a stray ``*.tmp.*`` (ignored by every reader
    and by gc's chunk sweep) but never a truncated object under its final
    name — which matters doubly now that chunks are shared across runs."""
    tmp = path + f".tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)          # atomic: crash-safe
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


STALE_TMP_S = 60.0       # a live writer holds its tmp far less


def _reclaim_stale_tmp(path: str, now: float) -> int:
    """Delete one stray ``*.tmp.*`` file if it is old enough that no live
    writer can still own it. Returns 1 if reclaimed."""
    if ".tmp." not in os.path.basename(path):
        return 0
    try:
        if now - os.path.getmtime(path) > STALE_TMP_S:
            os.remove(path)
            return 1
    except OSError:
        pass
    return 0


class _Pool:
    """What one handle knows of one chunk pool: the index of every pack it
    has read, and whether the pool holds loose chunk files (stores written
    before packs). The handle's lock guards every change."""

    def __init__(self, path: str):
        self.path = path
        self.packs_dir = os.path.join(path, "packs")
        self.packs: dict[str, dict[str, tuple[int, int]]] = {}
        self.where: dict[str, tuple[str, int, int]] = {}   # h -> location
        self.scanned = False
        self.loose = False

    def scan(self):
        """Read the index of every pack published since the last scan, and
        forget the packs that are gone."""
        try:
            names = os.listdir(self.packs_dir)
        except FileNotFoundError:
            names = []
        on_disk = {os.path.join(self.packs_dir, n) for n in names
                   if n.endswith(packs.SUFFIX)}
        for p in set(self.packs) - on_disk:
            self.forget(p)
        for p in sorted(on_disk - set(self.packs)):
            try:
                self.add(p, packs.read_index(p))
            except (FileNotFoundError, ValueError):
                pass    # gc removed it since the listing; not a whole pack
        if not self.scanned:
            try:
                self.loose = any(len(e) == 2 for e in os.listdir(self.path))
            except FileNotFoundError:
                pass
            self.scanned = True

    def add(self, path: str, index: dict):
        self.packs[path] = index
        for h, (off, n) in index.items():
            self.where.setdefault(h, (path, off, n))

    def forget(self, path: str):
        for h in self.packs.pop(path, {}):
            if self.where.get(h, (None,))[0] != path:
                continue
            del self.where[h]
            for other, idx in self.packs.items():   # another copy?
                if h in idx:
                    self.where[h] = (other, *idx[h])
                    break


def _manifest_chunk_hashes(manifest: dict):
    """Every chunk hash DIRECTLY listed by a manifest (no chain resolution —
    ancestors list their own)."""
    for leaf in manifest["leaves"]:
        for h in leaf.get("chunks") or []:
            if h is not None:
                yield h
        for h in (leaf.get("delta") or {}).values():
            yield h


def _manifest_enc_counts(manifest: dict) -> dict:
    """Per-encoding chunk counts of the chunks DIRECTLY listed by a manifest
    (chunks without a recorded encoding count as "raw")."""
    counts: dict[str, int] = {}
    for leaf in manifest.get("leaves") or []:
        enc = leaf.get("enc")
        for i, h in enumerate(leaf.get("chunks") or []):
            if h is None:
                continue
            e = enc[i] if enc else "raw"
            counts[e] = counts.get(e, 0) + 1
        denc = leaf.get("denc") or {}
        for i in (leaf.get("delta") or {}):
            e = denc.get(i, "raw")
            counts[e] = counts.get(e, 0) + 1
    return counts


_MEMBER_RE = None


def member_base(key: str) -> Optional[str]:
    """Base checkpoint key of a sharded MEMBER manifest name
    (``train_at_2.0.shard3`` -> ``train_at_2.0``; raw ``train@2.0.shard3``
    works too); ``None`` for non-member keys. Used by live-set construction
    (lineage.live_keys, context gc): a member whose global v4 stitch was
    never written — a host crashed between member publication and the
    stitch — must NOT seed the gc closure, or the orphans it left would be
    pinned forever. Members of STITCHED checkpoints need no seeding: the
    v4 manifest pulls them (and, through per-shard parent chains, every
    incomplete predecessor a later delta still inherits from) into the
    closure."""
    global _MEMBER_RE
    if _MEMBER_RE is None:
        import re
        _MEMBER_RE = re.compile(r"^(?P<base>.+)\.shard\d+$")
    m = _MEMBER_RE.match(key)
    return m.group("base") if m else None


def filter_orphan_members(keys: Iterable[str]) -> list[str]:
    """Drop member-manifest names whose base (stitched v4) key is absent
    from the SAME listing — the gc-seed form of the orphan rule above."""
    keys = list(keys)
    present = set(keys)
    return [k for k in keys
            if (lambda b: b is None or b in present)(member_base(k))]


def _safe(key: str) -> str:
    return key.replace("/", "_").replace("@", "_at_").replace(":", "_")
