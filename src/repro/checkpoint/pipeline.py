"""CheckpointPipeline: the delta-aware record-side checkpoint flow.

The paper's "lean checkpointing" thesis is that checkpoint cost should track
what CHANGED, not model size. This layer wires the device-side Pallas
fingerprint path end-to-end. One record path serves flat and mesh-sharded
checkpoints alike; its UNIT is a whole leaf when no mesh is set, or one
disjoint owner shard of a leaf on a mesh (checkpoint/mesh.py
``owned_shards``). Per unit the record path does, in order:

1. **Fingerprint + diff on device, fused** — `DeltaTracker` runs the fused
   Pallas fingerprint+changed kernel over the unit's own buffer (one read at
   HBM bandwidth produces BOTH the new digests and the change mask). Digests
   never leave the device; only the [G] change mask and the changed rows do.
2. **Transfer only changed chunks, wire-format** — exact leaves gather the
   changed u32 block rows; leaves matching the per-slot ``quantize_slots``
   policy run the fused gather+quantize kernel instead, so the rows leave
   the device already blockwise-int8 (q + scales — the q8 wire format, ~4x
   smaller than f32). On a frozen-majority workload the device->host
   traffic drops by the frozen fraction times the codec ratio —
   `transferred_bytes` in the per-checkpoint stats is this real DMA payload
   (wire-byte accounting), the honest M_i input for the adaptive
   controller's ε-overhead model.
3. **Write stage** (`AsyncWriter` job, FIFO on the writer thread) — hash the
   wire chunks (blake2b-16), store them content-addressed, and emit a
   **delta manifest**. In **overlap mode** (``overlap=True``) steps 1-2 are
   split: the training thread only DISPATCHES the fused fingerprint pass
   (digest state updates to async device arrays; no host sync), and the
   mask sync + gather + encode all run here on the writer thread — the
   foreground stall shrinks to kernel-launch time, and the bounded queue
   provides natural backpressure when the writer falls behind.

Delta manifest format (store manifest v3)::

    {
      "key": str, "version": 3,
      "kind": "full" | "delta",
      "parent": str | null,          # delta only: previous checkpoint key
      "treedef": str,
      "chunk_words": int,            # fingerprint chunk size in u32 words
      "meta": {...},
      "leaves": [{
         "path": str, "dtype": str, "shape": [int], "nbytes": int,
         "n_chunks": int,
         "leaf_enc": "q8"|"eb:...",  # slot POLICY, only when lossy
         "chunks": [hash, ...],      # kind == "full": complete ordered list
         "enc": [enc, ...],          # full only, parallel to chunks; only
                                     # present when any chunk is non-raw.
                                     # Per-chunk enc is "raw" | "q8" | "q4",
                                     # optionally suffixed "+z" when the
                                     # writer-thread entropy stage kept a
                                     # compressed payload
         "delta": {"<idx>": hash},   # kind == "delta": changed indices only
         "denc": {"<idx>": enc},     # delta only: non-raw changed chunks
      }, ...],
    }

v2 manifests (no per-chunk encodings — everything raw/exact) remain fully
readable; `resolve_manifest` inherits encodings through the parent chain
exactly like chunk hashes, and `get_tree` decodes non-raw chunks
transparently on restore (kernels.ops.decode_wire_chunk). Exact slots
restore bit-identical; q8 slots restore with per-element error bounded by
half a quantization step (absmax_block / 254), q4 by absmax_block / 14.
Slots declared via ``error_bounds`` pick, per changed chunk, the cheapest
encoding whose GUARANTEED bound (delta.Q4_ATOL_DIV / Q8_ATOL_DIV margins)
satisfies the slot's atol.

Mesh-aware record (``mesh=``): a unit's bytes only move device -> its
host's store shard, with no all-gather. The write stage groups the units by
store shard into one v3 member manifest each (``<key>.shard<h>`` chained to
``<parent>.shard<h>``, leaves ``<path>::shard<sid>`` with their ``bounds``),
then a v4 stitching manifest records the global layout (per-leaf shape,
recorded PartitionSpec, shard bounds + placement). A flat checkpoint is the
one group, written as one v3 manifest. The placement is part of the
structure signature, so a layout change forces a full manifest. See
checkpoint/mesh.py for the restore-side stitch/reshard geometry.

A delta manifest inherits every unlisted chunk hash from its parent chain
(`CheckpointStore.resolve_manifest`). Chains are bounded: a FULL manifest is
written (a) for the first checkpoint of a scope, (b) every `full_every`
checkpoints, and (c) whenever the leaf structure changes (leaf added or
removed, dtype or shape changed) — so restore never chases unbounded
history and structure changes never alias stale chunks. A leaf whose chunk
size in native bytes is `chunk_words * native_bytes_per_word(dtype)`; the
final chunk is truncated to the leaf's `nbytes`, so restored bytes
concatenate exactly.

Scopes: checkpoints of different SkipBlocks pass distinct `scope` ids, so
each block keeps its own digest state, parent chain and full-manifest
cadence — interleaved blocks never diff against each other's trees.

Cross-run warm start (run lineage): ``warm_start(scope, parent_key,
manifest, tree)`` seeds a scope's state from an ANCESTOR RUN's final
resolved manifest in a shared store — per-leaf structure signatures, the
writer-side full chunk-hash lists, and the device-side digests (rehydrated
by running the Pallas fingerprint path over the restored tree, the same one
read submit() would pay). The scope's parent key is set to the ancestor's
QUALIFIED key (``"<run_id>::<key>"``), so the FIRST checkpoint of a derived
run is already a delta manifest chained across the run boundary: a
fine-tune of a 96%-frozen model transfers and stores ~4% on its very first
checkpoint instead of re-recording the model. `CheckpointStore` resolves
the qualified parent chain transparently; `store.gc` retains it (see
checkpoint/lineage.py for the registry that decides which runs are live).
"""
from __future__ import annotations

import fnmatch
import math
import time
from typing import Any, Iterable, Optional

import numpy as np

from repro.checkpoint.async_writer import AsyncWriter
from repro.checkpoint.delta import DeltaTracker, blocks_to_native_bytes
from repro.kernels.ops import (Q4_BLOCK, Q8_BLOCK, native_bytes_per_word,
                               q4_encode_chunk, q8_encode_chunk,
                               quantizable_dtype)
from repro.parallel.compression import entropy_encode_bytes
from repro.utils.timing import span

DEFAULT_FULL_EVERY = 8
# fallback hop cost for full_every="auto" before any replay calibration has
# been learned — mirror of replay.plan.RESTORE_HOP_S (kept local: pipeline
# must not import the replay layer)
DEFAULT_HOP_S = 0.002
# storage/fingerprint granularity: 16384 u32 words = 64 KiB chunks for
# 4-byte dtypes. Finer chunks transfer marginally less but cost one object
# FILE per chunk — at 4 KiB the filesystem round-trips dominate the write
# stage. 64 KiB keeps a [8, 16384] u32 fingerprint tile at 512 KiB of VMEM.
PIPELINE_CHUNK_WORDS = 16 * 1024


class CheckpointPipeline:
    def __init__(self, store, *, chunk_words: int = PIPELINE_CHUNK_WORDS,
                 full_every=DEFAULT_FULL_EVERY,
                 async_stage: bool = True, max_queue: int = 2,
                 on_materialized=None,
                 quantize_slots: Optional[Iterable[str]] = None,
                 error_bounds: Optional[dict] = None,
                 entropy: bool = True,
                 overlap: bool = False,
                 mesh=None, shard_axes: Iterable[str] = (),
                 dist=None):
        self.store = store
        self.chunk_words = chunk_words
        # full_every="auto": start at the default cadence and retune after
        # every full manifest from the store's learned read/hop costs — see
        # _retune_full_every. Restore-bound stores shorten chains; stores
        # with cheap manifest hops lengthen them.
        self.full_every_auto = (full_every == "auto")
        self.full_every = DEFAULT_FULL_EVERY if self.full_every_auto \
            else max(1, int(full_every))
        self.tracker = DeltaTracker(chunk_words)
        # mesh-aware record (units are owned shards): shard_axes picks which
        # mesh axes map onto store shards (default: all — one per device)
        self.mesh = mesh
        self.shard_axes = tuple(shard_axes or ())
        # true multi-process record: ``dist`` is a
        # parallel.rendezvous.StitchRendezvous carrying this process's
        # ProcessGroup. Each process fingerprints/gathers ONLY the shards
        # its devices own and writes ONLY its own hosts' member manifests;
        # the lead process gathers every host's publication through the
        # file barrier and writes the v4 stitch (or marks the checkpoint
        # incomplete past the deadline).
        self.dist = dist
        self._anchor = (0, 0)
        self._incomplete: list[str] = []
        self._key_chain: dict[str, list[str]] = {}
        if mesh is not None:
            from repro.checkpoint.mesh import (device_maps, local_anchor,
                                               mesh_meta)
            self._dev_ord, self._dev_host = device_maps(mesh,
                                                        self.shard_axes)
            self._mesh_meta = mesh_meta(mesh, self.shard_axes)
            if dist is not None:
                self._anchor = local_anchor(mesh, self._dev_ord,
                                            self._dev_host, 0)
        self._mesh_meta_written = False
        # per-slot lossy policy: leaf paths matching any of these names /
        # glob patterns are stored blockwise-int8 (q8 wire format) when the
        # dtype supports it. Empty (the default) = every leaf exact, so the
        # bit-identical restore invariant holds unless explicitly opted out.
        self.quantize_slots = tuple(quantize_slots or ())
        # declarative per-slot error bounds: {slot_or_glob: atol}. A matching
        # leaf uses the ADAPTIVE encoding selector — per changed chunk, the
        # cheapest wire encoding (q4 / q8 / raw) whose guaranteed blockwise
        # bound satisfies the atol. Takes precedence over quantize_slots.
        self.error_bounds = dict(error_bounds or {})
        # writer-thread entropy stage: byte-compress already-gathered wire
        # chunks of lossy-policy leaves off the step path (kept only when it
        # actually shrinks them). Requires the async stage — a sync pipeline
        # would pay it on the training thread, violating the epsilon budget.
        self.entropy = bool(entropy)
        # overlap mode defers mask-sync + gather to the writer thread; it
        # needs the async stage to exist (sync pipelines gain nothing)
        self.overlap = bool(overlap) and async_stage
        self._on_mat = on_materialized
        self.writer = AsyncWriter(store, max_queue=max_queue,
                                  on_materialized=self._materialized) \
            if async_stage else None
        # submit-side per-scope state (owned by the training thread)
        self._sig: dict[str, dict[str, tuple]] = {}
        self._last_key: dict[str, Optional[str]] = {}
        self._since_full: dict[str, int] = {}
        # writer-side per-scope state: path -> full ordered chunk-hash list
        # (and the parallel per-chunk encoding list). Only the writer thread
        # (or the inline sync path) touches them; jobs run FIFO so they
        # always reflect the previously written manifest.
        self._hashes: dict[str, dict[str, list]] = {}
        self._encs: dict[str, dict[str, list]] = {}
        self._stats: list[dict] = []

    def _slot_policy(self, pstr: str, dtype: str) -> str:
        """Per-leaf encoding POLICY: "eb:<atol>" when the leaf path matches
        an error_bounds entry (adaptive selector), "q8" when it matches a
        quantize_slots entry, "raw" otherwise. Both matchers take a slot
        name or a glob over the keystr path, and only fire when the dtype is
        one the fused quantize path supports. error_bounds wins when a leaf
        matches both."""
        if not quantizable_dtype(dtype):
            return "raw"
        for pat, atol in self.error_bounds.items():
            if _match_slot(pstr, pat):
                return f"eb:{float(atol):g}"
        for pat in self.quantize_slots:
            if _match_slot(pstr, pat):
                return "q8"
        return "raw"

    @staticmethod
    def _policy_delta_kwargs(policy: str) -> dict:
        """DeltaTracker kwargs for one leaf policy string."""
        if policy.startswith("eb:"):
            return {"error_bound": float(policy[3:])}
        if policy != "raw":
            return {"enc": policy}
        return {}

    # -------------------------------------------------------------- record --
    def submit(self, key: str, tree: Any, meta: Optional[dict] = None,
               scope: str = "default", block: bool = True) -> Optional[dict]:
        """Fingerprint `tree` unit by unit, transfer only changed chunks,
        and enqueue the write stage. Returns submit-side stats (or None when
        the writer queue is full and block=False — the checkpoint is skipped
        and the device digest state is rolled back so the next delta stays
        correct)."""
        import jax
        t_submit0 = time.perf_counter()
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        prev_sig = self._sig.get(scope, {})
        sig: dict[str, tuple] = {}
        units: list[dict] = []         # one per leaf, or per owned shard
        layout: list[dict] = []        # mesh only: the v4 manifest's leaves
        rollback: dict = {}
        tot = {"transferred_bytes": 0, "changed_chunks": 0, "copy_s": 0.0}
        # mesh only: per-host foreground cost. Hosts run concurrently in a
        # real deployment, so the simulated per-checkpoint wall is max over
        # hosts, not the serial sum this process pays
        stall: Optional[dict] = None if self.mesh is None else {}
        logical = 0
        total_chunks_n = 0
        structure_changed = False
        for path, leaf in flat:
            pstr = jax.tree_util.keystr(path)
            if not hasattr(leaf, "dtype"):     # Python int/float/bool leaf
                leaf = np.asarray(leaf)
            dtype = str(leaf.dtype)
            shape = list(getattr(leaf, "shape", ()))
            nbytes = _leaf_nbytes(leaf)
            policy = self._slot_policy(pstr, dtype)
            leaf_units = self._units(leaf, nbytes)
            # the policy and the placement are part of the structure
            # signature: flipping a slot's policy (or changing its error
            # bound) or its layout (resharded mid-run, mesh swap) forces a
            # FULL manifest and a digest reset, so a chain never inherits
            # chunks recorded under another encoding or covering other
            # bytes. Per-chunk choices WITHIN one "eb:" policy do not force
            # fulls — the manifest's enc/denc fields carry them.
            sig[pstr] = (dtype, tuple(shape), policy,
                         tuple((u["sid"], u["hid"],
                                tuple(map(tuple, u["bounds"])))
                               for u in leaf_units if u["sid"] is not None))
            if self.mesh is not None:
                from repro.checkpoint.mesh import leaf_spec_entries
                layout.append({"path": pstr, "dtype": dtype, "shape": shape,
                               "nbytes": nbytes,
                               "spec": (leaf_spec_entries(leaf) if nbytes
                                        else None),
                               "shards": [{"sid": u["sid"], "hid": u["hid"],
                                           "bounds": u["bounds"]}
                                          for u in leaf_units]})
            if nbytes == 0:
                # nothing to diff: a flat manifest lists the leaf with no
                # chunks; on a mesh it has no units, only its layout entry
                units += [{"path": pstr, "sid": None, "hid": None,
                           "bounds": None, "dtype": dtype, "shape": shape,
                           "nbytes": 0, "n_chunks": 0, "enc": "raw",
                           "changed_idx": [], "chunks": [],
                           "chunk_encs": []} for _ in leaf_units]
                continue
            tpaths = [f"{scope}::{pstr}" + ("" if u["sid"] is None
                                            else f"::s{u['sid']}")
                      for u in leaf_units]
            if prev_sig.get(pstr) != sig[pstr]:
                structure_changed = True
                # dtype change with identical block count would otherwise
                # slip through the digest comparison
                for tpath in tpaths:
                    self.tracker.forget(tpath)
            rollback.update(self.tracker.snapshot(tpaths))
            logical += nbytes
            dkw = self._policy_delta_kwargs(policy)
            for u, tpath in zip(leaf_units, tpaths):
                local = u["data"]
                lnb = _leaf_nbytes(local)
                ent = {"path": pstr, "sid": u["sid"], "hid": u["hid"],
                       "bounds": u["bounds"], "dtype": dtype,
                       "shape": list(getattr(local, "shape", ())),
                       "nbytes": lnb,
                       "n_chunks": -(-lnb // (self.chunk_words
                                              * native_bytes_per_word(dtype))),
                       "enc": policy}
                total_chunks_n += ent["n_chunks"]
                t0 = time.perf_counter()
                h = self.tracker.delta_dispatch(tpath, _fp_view(local),
                                                ckpt=key, **dkw)
                if stall is not None:
                    stall[u["hid"]] = stall.get(u["hid"], 0.0) \
                        + (time.perf_counter() - t0)
                if self.overlap:
                    # dispatch-only: mask sync, gather and encode run on
                    # the writer thread
                    ent["handle"] = h
                else:
                    self._finalize(ent, h, tot, stall)
                units.append(ent)
        if set(prev_sig) - set(sig):           # leaf removed
            structure_changed = True
        last = self._last_key.get(scope)
        since = self._since_full.get(scope, 0)
        full = (last is None or structure_changed
                or since + 1 >= self.full_every)
        payload = {
            "key": key, "scope": scope, "meta": meta or {},
            "kind": "full" if full else "delta",
            "parent": None if full else last,
            "treedef": str(treedef), "chunk_words": self.chunk_words,
            "units": units, "overlap": self.overlap,
            "logical_bytes": logical, "total_chunks": total_chunks_n,
        }
        # overlap mode: transferred/changed/copy are only known once the
        # writer thread finalizes the deferred gathers (None here; the
        # materialized stat carries the measured values)
        payload.update({k: None if self.overlap else v
                        for k, v in tot.items()})
        if self.mesh is not None:
            payload.update(mesh=self._mesh_meta, layout=layout,
                           shard_stall_s=stall)
        # foreground stall on the training thread (fused fingerprint + mask
        # sync + changed-row DMA — or dispatch-only in overlap mode): part
        # of the real M_i — the epsilon overhead invariant is meaningless if
        # this goes uncounted. It stops before the wait on the writer's
        # queue, which is the stat's queue_wait_s
        payload["submit_stall_s"] = time.perf_counter() - t_submit0
        if not self._dispatch(payload, block=block):
            # checkpoint skipped: next delta must still diff against the
            # last STORED checkpoint
            self.tracker.restore(rollback)
            return None
        self._sig[scope] = sig
        self._last_key[scope] = key
        self._since_full[scope] = 0 if full else since + 1
        if self.dist is not None:
            self._key_chain.setdefault(scope, []).append(key)
        out = {k: payload[k] for k in (
            "key", "kind", "parent", "transferred_bytes", "logical_bytes",
            "changed_chunks", "total_chunks", "overlap", "submit_stall_s")}
        if self.mesh is not None:
            out.update(sharded=True,
                       n_store_shards=self._mesh_meta["n_store_shards"],
                       shard_stall_s=dict(stall))
        return out

    def _units(self, leaf, nbytes: int) -> list[dict]:
        """The units one leaf records as, each {sid, hid, bounds, data}: the
        whole leaf when no mesh is set, else its disjoint owner shards (none
        for a zero-byte leaf)."""
        if self.mesh is None:
            return [{"sid": None, "hid": None, "bounds": None, "data": leaf}]
        if nbytes == 0:
            return []
        from repro.checkpoint.mesh import owned_shards
        return owned_shards(
            leaf, self._dev_ord, self._dev_host,
            process_index=(self.dist.group.process_id
                           if self.dist is not None else None),
            anchor=self._anchor)

    def _finalize(self, ent: dict, h: dict, tot: dict,
                  stall: Optional[dict]):
        """Second half of one unit's fused pass: sync its mask, gather (and
        quantize) its changed rows, encode them (in a ``flor.ckpt.encode``
        span) onto ``ent`` as ``changed_idx``, ``chunks`` and
        ``chunk_encs``, and add the transfer into ``tot`` — and the seconds
        into ``stall[hid]`` for a mesh unit."""
        t0 = time.perf_counter()
        d = self.tracker.finalize(h)
        with span("flor.ckpt.encode", ckpt=h["ckpt"]):
            idx, chunks, encs, t_bytes = _encode_changed(d, ent,
                                                         self.chunk_words)
        ent["changed_idx"], ent["chunks"], ent["chunk_encs"] = \
            idx, chunks, encs
        tot["transferred_bytes"] += t_bytes
        tot["changed_chunks"] += len(idx)
        tot["copy_s"] += d["copy_s"]
        if stall is not None:
            stall[ent["hid"]] = stall.get(ent["hid"], 0.0) \
                + (time.perf_counter() - t0)

    def _dispatch(self, payload: dict, block: bool) -> bool:
        # the job fills this dict with the write path's counters
        # (CheckpointStore.counting) and its own figures, and hands it on
        # as the checkpoint's stat; the submitting thread's queue wait lands
        # in it too, whenever the job runs
        stat = {"queue_wait_s": 0.0}

        def job(store):
            # the job's chunks go into a few packs, published before the
            # manifests that name them
            with store.counting(stat), store.packing():
                stat.update(self._write(payload, store))
            return stat
        if self.writer is not None:
            # a blocking put waits here while the writer's queue is full;
            # a non-blocking one returns at once and counts no wait
            with span("flor.ckpt.queue_wait", stat if block else None,
                      "queue_wait_s", ckpt=payload["key"]):
                return self.writer.submit_job(payload["key"], job,
                                              block=block)
        t0 = time.perf_counter()
        job(self.store)
        stat["materialize_s"] = time.perf_counter() - t0
        self._materialized(stat)
        return True

    def _write(self, payload: dict, store) -> dict:
        """Writer half of a checkpoint: finalize the deferred gathers
        (overlap mode), run the entropy stage, then write the units' chunks
        and one v3 manifest per store shard — the whole checkpoint's with no
        mesh; ``<key>.shard<h>`` members then the v4 stitch on a mesh.
        Members land BEFORE the stitch, so a crash can leave orphan members
        but never a v4 that references a missing one."""
        key, parent = payload["key"], payload["parent"]
        units = payload["units"]
        if payload["overlap"]:
            # deferred half of the fused pass: sync masks, gather (and
            # quantize) changed rows, encode wire payloads — all off the
            # training thread
            tot = {"transferred_bytes": 0, "changed_chunks": 0, "copy_s": 0.0}
            for u in units:
                h = u.pop("handle", None)
                if h is not None:
                    self._finalize(u, h, tot, payload.get("shard_stall_s"))
            payload.update(tot)
        entropy_s = sum(self._entropy_pass(u) for u in units)
        full = payload["kind"] == "full"
        if self.mesh is None:
            groups: dict = {None: units}
        else:
            groups = {}
            for u in units:
                groups.setdefault(u["hid"], []).append(u)
        new_bytes = new_chunks = 0
        members: dict[str, str] = {}
        shard_write_s: dict[int, float] = {}
        shard_bytes: dict[int, int] = {}
        for hid in sorted(groups):
            t0 = time.perf_counter()
            leaves, nb, nc, stored = self._write_units(groups[hid], store,
                                                       hid, payload)
            new_bytes, new_chunks = new_bytes + nb, new_chunks + nc
            name, mparent, member = key, parent, {}
            if hid is not None:     # a member: chained to the parent's
                name, member = f"{key}.shard{hid}", {"store_shard": hid}
                mparent = f"{parent}.shard{hid}" if parent else None
            store.put_manifest({
                "key": name, "version": 3, "kind": payload["kind"],
                "parent": mparent, "treedef": payload["treedef"],
                "chunk_words": payload["chunk_words"], **member,
                "meta": payload["meta"] if hid is None else {},
                "leaves": leaves})
            members[str(hid)] = name
            shard_write_s[hid] = time.perf_counter() - t0
            if stored:
                shard_bytes[hid] = stored
        if full:    # drop units that left the tree
            hashes_map = self._hashes.setdefault(payload["scope"], {})
            encs_map = self._encs.setdefault(payload["scope"], {})
            for stale in set(hashes_map) - {_writer_key(u) for u in units}:
                del hashes_map[stale]
                encs_map.pop(stale, None)
        # stitched: True = written (or flat), False = marked incomplete,
        # None = outcome unknown here (non-lead of a distributed fleet; the
        # lead decides, close() reconciles the tips from the store)
        stitched: Optional[bool] = True
        if self.mesh is not None:
            if self.dist is None:
                _put_stitch(store, payload, members, payload["layout"])
            else:
                stitched = self._dist_stitch(payload, store, members)
            if not self._mesh_meta_written and \
                    (self.dist is None or self.dist.group.is_lead):
                store.put_meta("mesh", payload["mesh"])
                self._mesh_meta_written = True
        if full and stitched:
            self._retune_full_every(store, payload["logical_bytes"])
        stat = {k: payload[k] for k in (
            "key", "kind", "parent", "transferred_bytes", "copy_s",
            "logical_bytes", "changed_chunks", "total_chunks",
            "submit_stall_s", "overlap")}
        stat.update(new_bytes=new_bytes, new_chunks=new_chunks)
        if self.mesh is None:
            stat["stored_bytes"] = sum(shard_bytes.values())
        else:
            stat.update(sharded=True, stitched=stitched,
                        n_store_shards=len(groups),
                        shard_stall_s=dict(payload["shard_stall_s"]),
                        shard_write_s=shard_write_s, shard_bytes=shard_bytes)
        stat.update(entropy_s=entropy_s, full_every=self.full_every)
        return stat

    def _write_units(self, units: list, store, shard,
                     payload: dict) -> tuple[list, int, int, int]:
        """Write the units' changed chunks into store shard ``shard``'s pool
        (the default pool when None), update the writer-side full hash and
        encoding lists of each unit (keyed by ``_writer_key``), and return
        (manifest leaves, new_bytes, new_chunks, stored_bytes). A full
        manifest lists a unit's chunks whole; a delta only the changed
        ones."""
        hashes_map = self._hashes.setdefault(payload["scope"], {})
        encs_map = self._encs.setdefault(payload["scope"], {})
        full = payload["kind"] == "full"
        new_bytes = new_chunks = stored_bytes = 0
        leaves = []
        for u in units:
            wkey, n = _writer_key(u), u["n_chunks"]
            cencs = u.get("chunk_encs") or ["raw"] * len(u["changed_idx"])
            base = hashes_map.get(wkey)
            base = [None] * n if base is None or len(base) != n \
                else list(base)
            ebase = encs_map.get(wkey)             # pre-v3 state: raw
            ebase = ["raw"] * n if ebase is None or len(ebase) != n \
                else list(ebase)
            delta_hashes = {}
            for i, data, ce in zip(u["changed_idx"], u["chunks"], cencs):
                h, nb, new = store.put_chunk(data, shard=shard)
                base[i] = h
                ebase[i] = ce
                delta_hashes[str(i)] = h
                new_bytes += nb
                new_chunks += int(new)
                stored_bytes += len(data)
            if any(h is None for h in base):
                raise RuntimeError(
                    f"delta pipeline inconsistency for {wkey!r}: unchanged "
                    f"chunks have no known hash (manifest kind "
                    f"{payload['kind']!r})")
            hashes_map[wkey] = base
            encs_map[wkey] = ebase
            mleaf = {"path": wkey, "dtype": u["dtype"], "shape": u["shape"],
                     "nbytes": u["nbytes"], "n_chunks": n}
            if u["sid"] is not None:
                mleaf["bounds"] = u["bounds"]
            if u["enc"] != "raw":
                # leaf-level POLICY (what this pipeline writes), distinct
                # from the per-chunk enc lists below: warm_start seeds the
                # structure signature from it
                mleaf["leaf_enc"] = u["enc"]
            if full:
                mleaf["chunks"] = base
                if any(e != "raw" for e in ebase):
                    mleaf["enc"] = ebase
            else:
                mleaf["delta"] = delta_hashes
                denc = {str(i): ce
                        for i, ce in zip(u["changed_idx"], cencs)
                        if ce != "raw"}
                if denc:
                    mleaf["denc"] = denc
            leaves.append(mleaf)
        return leaves, new_bytes, new_chunks, stored_bytes

    def _entropy_pass(self, leaf: dict) -> float:
        """Writer-thread entropy stage for one leaf: byte-compress its wire
        chunks in place (suffixing the chunk encoding with "+z") when the
        leaf has a lossy policy and compression actually pays — a payload is
        kept only below 0.95x its original size, so restore never decodes a
        compression pass that bought nothing. Runs ONLY when an async writer
        exists; on a sync pipeline this stage would land on the training
        thread and silently inflate the foreground stall. Returns seconds
        spent (the caller reports them as ``entropy_s`` so the adaptive
        controller can move them to the background accumulator)."""
        if self.writer is None or not self.entropy:
            return 0.0
        if leaf.get("enc", "raw") == "raw" or not leaf.get("chunks"):
            return 0.0
        t0 = time.perf_counter()
        chunks = leaf["chunks"]
        cencs = list(leaf.get("chunk_encs")
                     or ["raw"] * len(chunks))
        # raw chunks of a lossy-policy leaf (adaptive selector fallback) are
        # still float words — byte-plane shuffle at the dtype's width;
        # q8/q4 payloads are already byte-homogeneous, stride 1
        raw_isz = 2 if leaf["dtype"] in ("bfloat16", "float16") else 4
        for j, (data, ce) in enumerate(zip(chunks, cencs)):
            if ce.endswith("+z"):
                continue
            z = entropy_encode_bytes(
                data, itemsize=raw_isz if ce == "raw" else 1)
            if len(z) < 0.95 * len(data):
                chunks[j] = z
                cencs[j] = ce + "+z"
        leaf["chunk_encs"] = cencs
        return time.perf_counter() - t0

    def _retune_full_every(self, store, full_bytes: int):
        """Close the loop on the full-manifest cadence (full_every="auto"):
        pick the chain length K whose worst-case replay overhead — K
        manifest hops — costs about half the time re-reading a full
        checkpoint does, using the store's measured read bandwidth and the
        learned per-hop resolve cost (PR-6 restore calibration). A
        restore-bound store (expensive hops) gets short chains; a store with
        cheap local hops amortizes fulls over long ones. Runs on the writer
        thread right after each full manifest; submit() reads the updated
        value for the next cadence decision."""
        if not self.full_every_auto:
            return
        calib = store.get_meta("store_calib") or {}
        read_bps = float(calib.get("read_bps") or calib.get("write_bps")
                         or 1e9)
        hop_s = float(calib.get("hop_s") or DEFAULT_HOP_S)
        full_read_s = full_bytes / max(read_bps, 1.0)
        self.full_every = min(64, max(2, int(0.5 * full_read_s
                                             / max(hop_s, 1e-9))))

    # ------------------------------------------------- distributed stitch --
    def _dist_stitch(self, payload: dict, store,
                     members: dict) -> Optional[bool]:
        """Multi-process tail of a sharded checkpoint (writer thread).
        Every process PUBLISHES its member-manifest names + local layout
        fragment through the file rendezvous; the LEAD process gathers all
        publications, validates them, merges the global layout, and writes
        the v4 stitch atomically. Publication order is the crash-safety
        invariant: member manifests land before the marker, the marker
        before the stitch — so a crash anywhere in between leaves only
        unreferenced members (GC food), never a v4 naming a missing one.
        Past the deadline (or on validation failure) the lead marks the
        checkpoint ``incomplete`` in run meta and training moves on.

        Returns the stitch outcome on the lead (True = v4 written, False =
        incomplete); ``None`` on non-leads, whose publication returns long
        before the lead's verdict exists — their stats must not claim an
        outcome, and close() reconciles their tips from the store."""
        import os as _os
        from repro.parallel import rendezvous as rdv
        key = payload["key"]
        group = self.dist.group
        if rdv.crash_requested(key, group.process_id):
            # fault injection: die AFTER member publication, BEFORE the
            # marker — the exact window the crash-safety argument is about
            _os._exit(rdv.CRASH_EXIT_CODE)
        self.dist.publish(key, {
            "process": group.process_id,
            "kind": payload["kind"],
            "members": dict(members),
            "layout_shards": {lf["path"]: lf["shards"]
                              for lf in payload["layout"]},
        })
        if not group.is_lead:
            return None      # publication done; outcome is the lead's call
        got = self.dist.gather(key)
        merged = self._merge_markers(store, payload, got) \
            if got is not None else None
        if merged is None:
            self._mark_incomplete(store, key)
            return False
        layout, all_members = merged
        _put_stitch(store, payload, all_members, layout)
        self.dist.clear(key)
        return True

    def _merge_markers(self, store, payload: dict,
                       got: list) -> Optional[tuple]:
        """Validate every host's publication and merge the global (layout,
        members). None on any inconsistency — a member manifest missing
        from disk, a host that decided a different full/delta kind, or a
        shard set that does not tile a leaf — so a bad fleet state becomes
        an ``incomplete`` checkpoint instead of a corrupt stitch."""
        all_members: dict[str, str] = {}
        for marker in got:
            if marker.get("kind") != payload["kind"]:
                return None
            for hid, mkey in marker["members"].items():
                if not store.has(mkey):
                    return None
                all_members[str(hid)] = mkey
        layout = []
        for lf in payload["layout"]:
            merged = {k: v for k, v in lf.items() if k != "shards"}
            shards: list[dict] = []
            for marker in got:
                shards.extend(marker["layout_shards"].get(lf["path"], []))
            shards.sort(key=lambda s: s["sid"])
            merged["shards"] = shards
            layout.append(merged)
            if lf["nbytes"] > 0 and lf["shape"]:
                covered = sum(math.prod(max(0, hi - lo)
                                        for lo, hi in s["bounds"])
                              for s in shards)
                if covered != math.prod(int(d) for d in lf["shape"]):
                    return None    # shards don't tile the leaf
        return layout, all_members

    def _mark_incomplete(self, store, key: str):
        """Record a failed stitch in run meta (lead-only, so the
        read-modify-write never races): the replay planner skips these
        keys, and close() rolls final_keys back past them."""
        self._incomplete.append(key)
        cur = store.get_meta("incomplete_ckpts") or {"keys": []}
        if key not in cur["keys"]:
            cur["keys"].append(key)
        store.put_meta("incomplete_ckpts", cur)

    def _materialized(self, stat: dict):
        self._stats.append(stat)
        if self._on_mat:
            self._on_mat(stat)

    # ---------------------------------------------------------- warm start --
    def warm_start(self, scope: str, parent_key: str, manifest: dict,
                   arrays_by_path: dict) -> dict:
        """Seed one scope's record state from an ancestor run's final
        RESOLVED manifest, so the next submit() is a delta against it.

        `parent_key` must be the key the shared store resolves the manifest
        under — QUALIFIED (``"run::key"``) when it lives in another run's
        namespace. `manifest` is the ``resolve_manifest`` output (complete
        chunk lists per leaf); `arrays_by_path` the restored host arrays
        keyed by leaf path (``get_tree`` with no `like`). Seeds:

        * structure signatures — so the first submit is not forced full;
        * writer-side chunk-hash lists — so unchanged chunks inherit the
          ancestor's hashes instead of tripping the consistency check;
        * device digests — rehydrated with the Pallas fingerprint over the
          restored bytes, so only truly-changed chunks transfer.

        Call before the scope's first submit (its writer-side state is not
        yet shared with the writer thread). Raises ValueError when the
        manifest cannot seed this pipeline (v1, unresolved holes, different
        `chunk_words`) — the caller falls back to a cold start."""
        if manifest.get("kind") == "sharded":
            raise ValueError(
                f"warm start from sharded (v4) manifest {manifest['key']!r} "
                "is not supported yet — the derived run records cold")
        if manifest.get("version", 1) < 2:
            raise ValueError(
                f"warm start needs a v2 pipeline manifest; {manifest['key']!r}"
                " is v1 (put_tree) and uses incompatible chunking")
        if int(manifest.get("chunk_words") or 0) != self.chunk_words:
            raise ValueError(
                f"chunk_words mismatch: manifest {manifest.get('chunk_words')}"
                f" vs pipeline {self.chunk_words} — digests would never match")
        sig: dict[str, tuple] = {}
        hashes: dict[str, list] = {}
        encs: dict[str, list] = {}
        seeded_bytes = 0
        for leaf in manifest["leaves"]:
            path = leaf["path"]
            chunks = leaf.get("chunks")
            if chunks is None or any(h is None for h in chunks):
                raise ValueError(
                    f"manifest {manifest['key']!r} is not resolved at leaf "
                    f"{path!r} — pass resolve_manifest() output")
            if path not in arrays_by_path:
                raise ValueError(f"restored tree is missing leaf {path!r}")
            sig[path] = (leaf["dtype"], tuple(leaf["shape"]),
                         leaf.get("leaf_enc", "raw"), ())
            hashes[path] = list(chunks)
            encs[path] = list(leaf.get("enc") or ["raw"] * len(chunks))
            nbytes = int(leaf.get("nbytes", 0))
            seeded_bytes += nbytes
            if nbytes > 0:
                self.tracker.seed(f"{scope}::{path}",
                                  _fp_view(arrays_by_path[path]))
        self._sig[scope] = sig
        self._hashes[scope] = hashes
        self._encs[scope] = encs
        self._last_key[scope] = parent_key
        self._since_full[scope] = 0
        return {"scope": scope, "parent": parent_key,
                "leaves": len(sig), "seeded_bytes": seeded_bytes}

    # ----------------------------------------------------------- lifecycle --
    def drain(self):
        if self.writer is not None:
            self.writer.drain()

    def close(self):
        if self.writer is not None:
            self.writer.close()
            self.writer = None
        if self.dist is not None:
            # roll each scope's tip back to the newest STITCHED key: a tail
            # checkpoint whose stitch never happened (crashed peer,
            # straggler past the deadline) has member manifests but no v4,
            # and final_keys must never name it. Non-lead processes learn
            # the outcome here, from the store, without extra coordination.
            for scope, chain in self._key_chain.items():
                if chain and not self.dist.group.is_lead:
                    self._await_stitch(chain[-1])
                live = [k for k in chain if self.store.has(k)]
                self._last_key[scope] = live[-1] if live else None

    def _await_stitch(self, key: str):
        """Non-lead close-time wait for the lead's verdict on the tip key:
        either the v4 appears or the key lands in the incomplete meta.
        Bounded by the stitch timeout — a dead lead costs one deadline,
        never a wedge."""
        deadline = time.monotonic() + self.dist.timeout_s
        while time.monotonic() < deadline:
            if self.store.has(key):
                return
            inc = self.store.get_meta("incomplete_ckpts") or {"keys": []}
            if key in inc.get("keys", []):
                return
            time.sleep(0.02)

    def tips(self) -> dict[str, str]:
        """{scope: the last stored checkpoint key of its delta chain}, for
        every scope that has one."""
        return {s: k for s, k in self._last_key.items() if k}

    def chain_keys(self) -> list[str]:
        """The tip checkpoint key of every scope's delta chain. A GC that
        runs mid-record MUST keep these live (their parent closure carries
        every chunk hash the next delta manifest will inherit)."""
        return list(self.tips().values())

    def reset(self):
        """Forget all digest / chain state (next submits are full)."""
        self.tracker.reset()
        self._sig.clear()
        self._last_key.clear()
        self._since_full.clear()
        self._hashes.clear()
        self._encs.clear()

    @property
    def stats(self) -> list[dict]:
        return list(self._stats)


def _encode_changed(d: dict, lmeta: dict, chunk_words: int):
    """Turn one finalized delta record into per-chunk wire payloads.

    Iterates the delta's ``enc_groups`` — one group per wire encoding the
    tracker chose (a fixed-policy leaf has at most one; the adaptive
    error-bound selector can split one checkpoint's changed chunks across
    q4 / q8 / raw). Raw rows: gathered u32 blocks back to native bytes,
    last chunk trimmed to the leaf's real length. q8 / q4 rows: already
    int8 (resp. packed-nibble) + scales from the fused gather kernels —
    packed into the self-describing chunk formats (per-chunk element count,
    so the last chunk trims the same way). Returns (idx_keep, chunks_keep,
    encs_keep, transferred_bytes) with the three lists parallel and sorted
    by chunk index."""
    nbytes, n_chunks = lmeta["nbytes"], lmeta["n_chunks"]
    dtype = lmeta["dtype"]
    itemsize = 2 if dtype in ("bfloat16", "float16") else 4
    total_elems = nbytes // itemsize
    chunk_native = chunk_words * native_bytes_per_word(dtype)
    out: dict[int, tuple[str, bytes]] = {}
    for gr in d["enc_groups"]:
        e = gr["enc"]
        if e in ("q8", "q4"):
            rows, encode, block = (gr["q"], q8_encode_chunk, Q8_BLOCK) \
                if e == "q8" else (gr["packed"], q4_encode_chunk, Q4_BLOCK)
            for j, i in enumerate(gr["idx"].tolist()):
                n_el = chunk_words if i < n_chunks - 1 \
                    else total_elems - (n_chunks - 1) * chunk_words
                out[int(i)] = (e, encode(rows[j], gr["scales"][j], n_el,
                                         min(block, chunk_words)))
        else:
            native = blocks_to_native_bytes(gr["blocks"], dtype)
            # tracker clamps changed_idx to the leaf's real chunk count, so
            # every row lands in [0, n_chunks); only the last needs trimming
            for i, data in zip(gr["idx"].tolist(), native):
                if i == n_chunks - 1:
                    data = data[: nbytes - (n_chunks - 1) * chunk_native]
                out[int(i)] = ("raw", data)
    idx_keep = sorted(out)
    encs_keep = [out[i][0] for i in idx_keep]
    chunks_keep = [out[i][1] for i in idx_keep]
    return idx_keep, chunks_keep, encs_keep, \
        sum(len(c) for c in chunks_keep)


def _match_slot(pstr: str, pat: str) -> bool:
    """True when a keystr leaf path matches a slot name or glob pattern."""
    return (f"['{pat}']" in pstr or f'["{pat}"]' in pstr
            or f".{pat}" in pstr or fnmatch.fnmatch(pstr, pat))


def _writer_key(unit: dict) -> str:
    """The writer-side key of one unit's hash and encoding lists, and its
    manifest leaf's path: the leaf path, ``::shard<sid>`` for a shard."""
    return unit["path"] if unit["sid"] is None \
        else f"{unit['path']}::shard{unit['sid']}"


def _fp_view(leaf):
    """The array the fingerprint actually runs over. 64-bit HOST leaves get
    a bit-preserving u32 view: jit would silently downcast them when jax x64
    is disabled, corrupting the stored bytes (native_bytes_per_word is 4
    either way). Shared by submit() and warm_start() so rehydrated digests
    are byte-for-byte comparable with recorded ones."""
    if isinstance(leaf, np.ndarray) and leaf.dtype.itemsize == 8:
        return np.ascontiguousarray(leaf).reshape(-1).view(np.uint32)
    return leaf


def _leaf_nbytes(leaf) -> int:
    return int(leaf.nbytes if hasattr(leaf, "nbytes")
               else np.asarray(leaf).nbytes)


def _put_stitch(store, payload: dict, members: dict, layout: list):
    """Write a mesh checkpoint's v4 stitching manifest: the global layout
    and the member manifest of every store shard."""
    store.put_manifest({
        "key": payload["key"], "version": 4, "kind": "sharded",
        "ckpt_kind": payload["kind"], "parent": payload["parent"],
        "treedef": payload["treedef"], "chunk_words": payload["chunk_words"],
        "mesh": payload["mesh"], "members": members,
        "meta": payload["meta"], "leaves": layout,
    })
