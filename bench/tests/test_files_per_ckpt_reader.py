"""The reader of the writer's files per checkpoint, on hand-made
per-checkpoint stats: the mean of ``files_written``, and nothing where the
program has no such counter."""
from types import SimpleNamespace

import pytest

from harness.spec import Cell, load_benchmark, load_reader

STATS = [
    {"key": "a", "queue_wait_s": 20.0, "copy_s": 0.25,
     "transferred_bytes": 1_000_000_000, "compress_s": 18.0, "hash_s": 2.0,
     "file_s": 4.0},
    {"key": "b", "queue_wait_s": 18.0, "copy_s": 0.25,
     "transferred_bytes": 1_000_000_000, "compress_s": 16.0, "hash_s": 1.0,
     "file_s": 2.0},
]
CELLS = ("florbench-100m.dense_record", "granite-3-2b.frozen_ft_record")


def test_files_per_ckpt_averages_the_window_checkpoints():
    read = load_reader("writer_files_per_ckpt")
    stats = [dict(s, files_written=n) for s, n in zip(STATS, (20, 19))]
    assert read(SimpleNamespace(stats=stats)) == pytest.approx(19.5)


def test_files_per_ckpt_reads_nothing_without_the_counter():
    read = load_reader("writer_files_per_ckpt")
    # the parent program's stats: its counters, but no files_written
    assert read(SimpleNamespace(stats=STATS)) is None
    assert read(SimpleNamespace(stats=[])) is None


@pytest.mark.parametrize("cell", CELLS)
def test_both_cells_report_files_per_ckpt(cell):
    names = {m["name"] for m in Cell.find(load_benchmark(), cell).per_layer}
    assert "writer_files_per_ckpt" in names
