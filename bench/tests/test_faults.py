"""The harness with the timed path broken underneath: each fault a cell can
have turns ``correct`` false. The chip's look is skipped (rehearsal)."""
import time

import pytest
from tiny import tiny_cell

from harness.runner import run_cell


def _unchanged(system):
    def step(state, batch):
        _, metrics = system.step(state, batch)
        return state, metrics
    return step


def _half_batch(system):
    def step(state, batch):
        tokens = batch["tokens"]
        return system.step(state, {"tokens": tokens[: tokens.shape[0] // 2]})
    return step


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_step_is_not_correct(fault, tmp_path):
    res, lines = run_cell(tiny_cell(), 41, 0.3, False,
                          t_start=time.monotonic(), fault={"step": fault},
                          rehearsal=True, run_dir=tmp_path)
    assert res["correct"] is False
    assert any(line.endswith("FAILED") for line in lines)


def test_chunk_altered_where_written_is_not_correct(monkeypatch, tmp_path):
    from repro.checkpoint.store import CheckpointStore
    put = CheckpointStore.put_chunk

    def altered(self, data, shard=None):
        return put(self, bytes([data[0] ^ 1]) + bytes(data[1:]), shard)
    monkeypatch.setattr(CheckpointStore, "put_chunk", altered)
    res, _ = run_cell(tiny_cell(), 43, 0.3, False, t_start=time.monotonic(),
                      rehearsal=True, run_dir=tmp_path)
    assert res["correct"] is False
    assert res["checks"]["store_mismatch"]["value"] > 0
