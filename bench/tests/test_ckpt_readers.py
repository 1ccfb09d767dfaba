"""The readers of the record path's checkpoint counters, on hand-made
per-checkpoint stats: each gives its value, and nothing where the program
has no such counter."""
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
NEW = {"ckpt_queue_wait_ms": 19_000.0, "d2h_gb_per_s": 4.0,
       "writer_compress_ms": 17_000.0, "writer_hash_ms": 1_500.0,
       "writer_file_ms": 3_000.0}
OLD = ("ckpt_stall_ms", "writer_mb_per_s", "fingerprint_roofline",
       "train_step_ms", "mfu.record", "device_idle.record")
CELLS = ("florbench-100m.dense_record", "granite-3-2b.frozen_ft_record")


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_averages_the_window_checkpoints(name):
    read = load_reader(name)
    assert read(SimpleNamespace(stats=STATS)) == pytest.approx(NEW[name])


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_reads_nothing_without_the_counter(name):
    read = load_reader(name)
    # the parent program's stats: no new counter on any checkpoint
    old = [{"key": s["key"], "transferred_bytes": s["transferred_bytes"],
            "submit_stall_s": 0.5} for s in STATS]
    assert read(SimpleNamespace(stats=old)) is None
    assert read(SimpleNamespace(stats=[])) is None


def test_copy_rate_reads_nothing_without_copy_time():
    stats = [dict(s, copy_s=0.0) for s in STATS]
    assert load_reader("d2h_gb_per_s")(SimpleNamespace(stats=stats)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_both_cells_report_every_per_layer_metric(cell):
    names = {m["name"] for m in Cell.find(load_benchmark(), cell).per_layer}
    assert set(OLD) | set(NEW) <= names
