"""The control at a size a test can hold: the reference in float8 put in the
program's place reads as not correct under the tiny cell's limits, through
the harness's own comparison, while the program reads as correct; so does
the half-batch fault."""
import time

from tiny import tiny_cell

import calibrate
from harness.runner import run_cell


def test_control_fails_and_program_passes():
    rows = calibrate.readings(tiny_cell(), [5, 6], 2, emit=lambda r: None)
    for r in rows:
        assert r["correct"] is (r["kind"] == "program"), r


def test_control_in_the_run_is_not_correct(tmp_path):
    res, lines = run_cell(tiny_cell(), 47, 0.3, False,
                          t_start=time.monotonic(), control=True,
                          rehearsal=True, run_dir=tmp_path)
    assert res["correct"] is False
    assert res["checks"]["store_mismatch"]["value"] == 0
    assert any(line.endswith("FAILED") for line in lines)
