"""Device-side delta detection for lean checkpointing.

The host-side content-addressed store already avoids STORING unchanged
chunks; this layer avoids TRANSFERRING them. Per leaf it keeps the previous
checkpoint's per-chunk digests on device; at checkpoint time the Pallas
fingerprint kernel (kernels/chunk_delta.py) produces new digests in one read
of the leaf, and only rows with changed digests are gathered and copied to
host. On fine-tuning-shaped workloads (frozen experts/embeddings) this cuts
device->host traffic by the frozen fraction — the same economics as the
paper's lean checkpointing, one level lower.

`CheckpointPipeline` (checkpoint/pipeline.py) is the consumer: it turns the
gathered u32 blocks back into native leaf bytes (`blocks_to_native_bytes`)
and hands them to the writer stage.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import (CHUNK_WORDS, chunk_absmax,
                               fingerprint_and_changed, fingerprint_leaf,
                               gather_changed_blocks, gather_quantize4_blocks,
                               gather_quantize_blocks, native_bytes_per_word)

# Error-bound encoding selector thresholds. The TRUE per-element bound of a
# blockwise codec is half a quantization step: absmax/254 for q8 (scale =
# absmax/127), absmax/14 for q4 (scale = absmax/7). The selector divides by
# smaller figures so f32 scale rounding can never push a chunk past its
# declared atol — the bound it GUARANTEES is absmax/Q8_ATOL_DIV (resp. q4).
Q8_ATOL_DIV = 126.0
Q4_ATOL_DIV = 13.5


def blocks_to_native_bytes(blocks: np.ndarray, dtype) -> list[bytes]:
    """Convert gathered [C, W] uint32 blocks back to the original array's
    byte representation, one bytes object per chunk. Inverts the dtype
    widening of kernels.ops._as_u32_blocks (each word carries
    `native_bytes_per_word(dtype)` original bytes; padding words at the tail
    of the last chunk are zeros and are truncated by the caller)."""
    bpw = native_bytes_per_word(dtype)
    blocks = np.ascontiguousarray(blocks, dtype=np.uint32)
    if bpw == 4:
        rows = blocks
    elif bpw == 2:
        rows = blocks.astype(np.uint16)
    else:
        rows = blocks.astype(np.uint8)
    return [rows[i].tobytes() for i in range(rows.shape[0])]


def _grid_rows(nbytes: int, bpw: int, chunk_words: int) -> int:
    """Rows of the [G, chunk_words] block view a leaf of `nbytes` produces
    (mirrors kernels.ops._as_u32_blocks padding: G is TILE_G-aligned)."""
    n = max(1, nbytes // bpw)
    g = -(-n // chunk_words)
    return -(-g // 8) * 8


class DeltaTracker:
    def __init__(self, chunk_words: int = CHUNK_WORDS):
        self.chunk_words = chunk_words
        self._digests: dict[str, jnp.ndarray] = {}

    def delta_dispatch(self, path: str, leaf, *, quantize: bool = False,
                       enc: str = None, error_bound: float = None) -> dict:
        """Phase 1 of a delta: launch the device work (fused fingerprint +
        changed-mask when a previous digest exists) WITHOUT any host sync,
        and update the stored digest to the new device array. Returns an
        opaque handle for :meth:`finalize`. The overlap-mode pipeline calls
        this on the training thread (dispatch-only cost) and finalizes on
        the writer thread; the synchronous path composes both in
        :meth:`delta`.

        Encoding selection: ``enc`` fixes the wire encoding of every changed
        chunk ("raw" | "q8" | "q4"; ``quantize=True`` is the legacy spelling
        of enc="q8"). ``error_bound`` switches to the ADAPTIVE selector
        instead: a per-chunk absmax pass (``chunk_absmax``, one extra leaf
        read, dispatched async here) lets finalize pick, per changed chunk,
        the cheapest encoding whose guaranteed bound satisfies the atol —
        q4 when absmax/13.5 <= atol, else q8 when absmax/126 <= atol, else
        raw. Float leaves only (the caller gates on quantizable_dtype).

        The handle retains references to `leaf` and the new digest — safe
        for jax arrays because nothing in this codebase donates buffers, so
        a deferred finalize gathers from the exact submitted state even if
        the caller keeps training. Host numpy leaves are retained by
        REFERENCE: a caller that mutates one in place between dispatch and
        finalize would gather post-mutation bytes (functional updates, the
        norm here, are unaffected)."""
        if enc is None:
            enc = "q8" if quantize else "raw"
        if error_bound is not None:
            enc = "auto"
        nbytes = int(leaf.nbytes) if hasattr(leaf, "nbytes") \
            else int(np.asarray(leaf).nbytes)
        dtype = leaf.dtype if hasattr(leaf, "dtype") \
            else np.asarray(leaf).dtype
        bpw = native_bytes_per_word(dtype)
        prev = self._digests.get(path)
        if prev is not None \
                and int(prev.shape[0]) == _grid_rows(nbytes, bpw,
                                                     self.chunk_words):
            digest, mask = fingerprint_and_changed(leaf, prev,
                                                   self.chunk_words)
            first = False
        else:
            digest = fingerprint_leaf(leaf, self.chunk_words)
            mask = None
            first = True                              # first sight: all new
        self._digests[path] = digest
        absmax = chunk_absmax(leaf, self.chunk_words) if enc == "auto" \
            else None
        return {"path": path, "leaf": leaf, "digest": digest, "mask": mask,
                "first": first, "enc": enc, "quantize": (enc == "q8"),
                "error_bound": error_bound, "absmax": absmax,
                "nbytes": nbytes, "bpw": bpw}

    def _gather_group(self, h: dict, enc: str, idx: np.ndarray,
                      n_real: int) -> dict:
        """Gather one encoding group's changed rows off the device. The
        gather width pads to the next power of two (capped at the chunk
        count) so fluctuating change counts compile O(log G) gather variants
        per leaf instead of one per novel count. The index vector stays a
        host array, so it lands on the leaf's own device, not the default
        one. Returns {enc, idx, bytes, <wire arrays per encoding>}."""
        c = int(idx.size)
        cap = min(1 << (c - 1).bit_length(), n_real)
        idx_pad = np.concatenate(
            [idx, np.full(cap - c, idx[0], idx.dtype)]).astype(np.int32)
        if enc == "q8":
            q, s = gather_quantize_blocks(h["leaf"], idx_pad,
                                          self.chunk_words)
            q = np.ascontiguousarray(np.asarray(jax.device_get(q))[:c])
            s = np.ascontiguousarray(np.asarray(jax.device_get(s))[:c])
            return {"enc": "q8", "idx": idx, "q": q, "scales": s,
                    "bytes": int(q.nbytes + s.nbytes)}
        if enc == "q4":
            p, s = gather_quantize4_blocks(h["leaf"], idx_pad,
                                           self.chunk_words)
            p = np.ascontiguousarray(np.asarray(jax.device_get(p))[:c])
            s = np.ascontiguousarray(np.asarray(jax.device_get(s))[:c])
            return {"enc": "q4", "idx": idx, "packed": p, "scales": s,
                    "bytes": int(p.nbytes + s.nbytes)}
        rows = np.asarray(jax.device_get(gather_changed_blocks(
            h["leaf"], idx_pad, self.chunk_words)))
        rows = np.ascontiguousarray(rows[:c])
        return {"enc": "raw", "idx": idx, "blocks": rows,
                "bytes": int(rows.nbytes)}

    def finalize(self, h: dict) -> dict:
        """Phase 2: sync the change mask, gather the changed rows in wire
        form per the handle's encoding (fixed raw/q8/q4, or the adaptive
        error-bound selector), and return the delta record. Touches no
        tracker state, so it is safe to run on the writer thread while the
        training thread keeps dispatching.

        Returns {digest, mask (np bool [G]), enc_groups ([{enc, idx, ...}]
        — one group per distinct wire encoding chosen), changed_idx,
        transferred_bytes, total_bytes} plus the legacy single-encoding
        fields (changed_blocks for raw handles, changed_q/changed_scales
        for q8) older callers still read."""
        digest = h["digest"]
        g = int(digest.shape[0])
        if h["first"]:
            mask = np.ones((g,), bool)
        else:
            mask = np.asarray(jax.device_get(h["mask"])).astype(bool)
        nbytes, bpw = h["nbytes"], h["bpw"]
        n_real = max(1, -(-nbytes // (self.chunk_words * bpw)))
        idx = np.flatnonzero(mask[:n_real])
        enc = h.get("enc", "q8" if h.get("quantize") else "raw")
        groups: list[dict] = []
        transferred = 0
        if idx.size:
            if enc == "auto":
                # per-chunk selector: the cheapest encoding whose GUARANTEED
                # bound (absmax / divisor) satisfies the slot's atol
                amax = np.asarray(jax.device_get(h["absmax"]))[idx]
                atol = float(h["error_bound"])
                pick = np.where(
                    amax / Q4_ATOL_DIV <= atol, "q4",
                    np.where(amax / Q8_ATOL_DIV <= atol, "q8", "raw"))
                for e in ("q4", "q8", "raw"):
                    sub = idx[pick == e]
                    if sub.size:
                        groups.append(self._gather_group(h, e, sub, n_real))
            else:
                groups.append(self._gather_group(h, enc, idx, n_real))
            transferred = sum(gr["bytes"] for gr in groups)
        # legacy single-encoding view (raw/q8 callers predate enc_groups)
        changed = None
        changed_q = changed_scales = None
        if enc == "raw":
            changed = groups[0]["blocks"] if groups \
                else np.zeros((0, self.chunk_words), np.uint32)
        elif enc == "q8" and groups:
            changed_q = groups[0]["q"]
            changed_scales = groups[0]["scales"]
        return {
            "digest": np.asarray(jax.device_get(digest)),
            "mask": mask,
            "changed_blocks": changed,
            "changed_q": changed_q,
            "changed_scales": changed_scales,
            "enc_groups": groups,
            "changed_idx": idx,
            "transferred_bytes": transferred,
            "total_bytes": int(g * self.chunk_words * 4),
        }

    def delta(self, path: str, leaf, *, quantize: bool = False,
              enc: str = None, error_bound: float = None) -> dict:
        """Synchronous delta: dispatch + finalize in one call (see the two
        phases above). Updates the stored digest — call exactly once per
        MATERIALIZED checkpoint so the mask always means "changed since the
        last stored checkpoint".

        Host traffic per call: the [G] change mask (one small device_get —
        jnp.nonzero's implicit size sync cost more than the mask itself),
        the [G,2] digest, and the changed rows. Rows past the leaf's real
        byte length (block-padding to the kernel tile) are never gathered,
        and a fully-unchanged leaf costs ONLY the fused fingerprint read —
        the u32 block view is never materialized for it.
        """
        return self.finalize(self.delta_dispatch(path, leaf,
                                                 quantize=quantize, enc=enc,
                                                 error_bound=error_bound))

    def seed(self, path: str, leaf):
        """Rehydrate one leaf's device-side digests from restored bytes
        (cross-run warm start): computes exactly the fingerprint submit()
        would via the same Pallas path, so the FIRST delta() of a derived
        run masks only chunks that truly changed since the ancestor run's
        final checkpoint. No mask, no gather — one fingerprint read."""
        self._digests[path] = fingerprint_leaf(leaf, self.chunk_words)

    def forget(self, path: str):
        """Drop one leaf's digests — the next delta() transfers everything
        (used when a leaf's dtype changes without changing its block count,
        which the digest comparison alone cannot flag as a full rewrite)."""
        self._digests.pop(path, None)

    def reset(self):
        self._digests.clear()
