"""Pack files: many compressed chunks in one append-only file.

A checkpoint of N chunks used to cost N files (tmp write and rename each);
on a filesystem that charges per file operation that, not the bytes, set
the writer's pace. A pack holds the chunk records of one writer job back to
back, then an index and a fixed trailer::

    record 0 | record 1 | ... | index | trailer

* a record is one chunk's compressed payload, byte for byte what a loose
  ``<h>.zst`` chunk file holds;
* the index has one ``ENTRY`` per record: the chunk's 16-byte blake2b
  digest, the record's offset (u64) and its length (u32), little-endian;
* the trailer (``TRAILER``) is the index's offset (u64), the number of
  records (u64) and ``MAGIC``.

A pack is written under a ``*.tmp.*`` name, one ``os.writev`` per
``WRITE_BYTES`` of records (no copy into a joined buffer: on a TPU v5e
host's checkout that copy cost more than the write), and published by one
``os.replace`` when it is closed: a reader never sees a partial pack, and a
killed writer leaves a tmp file no reader opens and gc reclaims once stale.
Packs are immutable once published; gc rewrites a partly dead pack into a
new one (``CheckpointStore.gc``).
"""
from __future__ import annotations

import os
import struct
import threading
import time

ENTRY = struct.Struct("<16sQI")
TRAILER = struct.Struct("<QQ8s")
MAGIC = b"FLORPAK1"
SUFFIX = ".pack"

PACK_BYTES = 64 * 1024 * 1024   # published once its records pass this
WRITE_BYTES = 4 * 1024 * 1024   # records reach the file in writes of this
_MAX_IOV = 512                  # records per writev, under IOV_MAX


def record_cost(offset: int, length: int) -> int:
    """On-disk bytes a record accounts for: its payload and index entry, and
    for a pack's first record (offset 0) the trailer too, so the costs of a
    pack's records sum to its file size."""
    return length + ENTRY.size + (TRAILER.size if offset == 0 else 0)


def read_index(path: str) -> dict[str, tuple[int, int]]:
    """{hex digest: (offset, length)} of a published pack. Raises
    ``ValueError`` for a file that is not a whole pack."""
    fd = os.open(path, os.O_RDONLY)
    try:
        size = os.fstat(fd).st_size
        if size < TRAILER.size:
            raise ValueError(f"{path}: {size} bytes, too short for a pack")
        idx_off, n, magic = TRAILER.unpack(
            os.pread(fd, TRAILER.size, size - TRAILER.size))
        if magic != MAGIC or idx_off + n * ENTRY.size + TRAILER.size != size:
            raise ValueError(f"{path}: no pack trailer")
        raw = os.pread(fd, n * ENTRY.size, idx_off)
    finally:
        os.close(fd)
    return {d.hex(): (off, length)
            for d, off, length in ENTRY.iter_unpack(raw)}


def read_record(path: str, offset: int, length: int) -> bytes:
    fd = os.open(path, os.O_RDONLY)
    try:
        data = os.pread(fd, length, offset)
    finally:
        os.close(fd)
    if len(data) != length:
        raise ValueError(f"{path}: record at {offset} cut short")
    return data


def _write_all(fd: int, bufs: list):
    n = os.writev(fd, bufs)
    for b in bufs:                     # a short write: the rest one by one
        if n >= len(b):
            n -= len(b)
            continue
        view = memoryview(b)[n:]
        while view:
            view = view[os.write(fd, view):]
        n = 0


class PackWriter:
    """One open pack in ``packs_dir``: ``add`` appends records to its tmp
    file, ``publish`` writes the index and trailer and renames it into
    place, ``abort`` removes it."""

    def __init__(self, packs_dir: str):
        # time first, so a listing sorts packs oldest first
        name = f"{time.time_ns():016x}{os.urandom(4).hex()}{SUFFIX}"
        self.path = os.path.join(packs_dir, name)
        self._tmp = f"{self.path}.tmp.{os.getpid()}.{threading.get_ident()}"
        self._fd = os.open(self._tmp, os.O_WRONLY | os.O_CREAT | os.O_EXCL,
                           0o644)
        self.entries: dict[str, tuple[int, int]] = {}
        self.size = 0                  # record bytes so far
        self._batch: list = []
        self._batch_bytes = 0

    def add(self, h: str, payload: bytes) -> int:
        """Append one record; returns its ``record_cost``."""
        off = self.size
        self.entries[h] = (off, len(payload))
        self.size += len(payload)
        self._batch.append(payload)
        self._batch_bytes += len(payload)
        if self._batch_bytes >= WRITE_BYTES or len(self._batch) >= _MAX_IOV:
            self._flush()
        return record_cost(off, len(payload))

    def _flush(self):
        if self._batch:
            _write_all(self._fd, self._batch)
            self._batch = []
            self._batch_bytes = 0

    def publish(self) -> int:
        """Write the index and trailer, close, and rename into place.
        Returns the pack's size in bytes."""
        index = b"".join(ENTRY.pack(bytes.fromhex(h), off, n)
                         for h, (off, n) in self.entries.items())
        self._batch.append(index + TRAILER.pack(self.size, len(self.entries),
                                                MAGIC))
        self._flush()
        os.close(self._fd)
        self._fd = None
        os.replace(self._tmp, self.path)
        return self.size + len(index) + TRAILER.size

    def abort(self):
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None
        try:
            os.remove(self._tmp)
        except FileNotFoundError:
            pass
