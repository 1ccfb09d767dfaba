"""A smoke-size rehearsal of whole runs off the chip: the harness's path
from set-up through the window to the comparison, with no device metric."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest
from tiny import tiny_cell

from harness.runner import run_cell
from harness.spec import BENCH, ROOT


@pytest.mark.parametrize("trainable", ["all", {"top_layers": 1}])
def test_rehearsal_is_correct_and_reports_no_device_metric(trainable,
                                                           tmp_path):
    res, lines = run_cell(tiny_cell(trainable), 2**31 + 77, 0.5, False,
                          t_start=time.monotonic(), rehearsal=True,
                          run_dir=tmp_path)
    assert res["correct"] is True
    assert res["metrics"] == {}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"loss_gap", "grad_gap", "update_gap",
                                  "store_mismatch", "window_compiles",
                                  "warmup_unsteady"}
    assert res["window"]["compiles"] == 0 and res["window"]["steady"]
    # the bytes measured from the store's files agree with the program's
    # own count of what the window's checkpoints wrote
    assert res["window"]["stored_bytes"] == res["window"]["new_bytes_counted"] > 0
    assert res["attempted"] == res["window"]["steps"] > 0
    assert len(lines) == 6 and all(line.endswith("ok") for line in lines)


def test_warmup_that_ends_unsteady_is_not_correct(tmp_path):
    # one warm-up interval submits the full checkpoint and no delta
    res, lines = run_cell(tiny_cell(max_warmup=1), 2**31 + 79, 0.3, False,
                          t_start=time.monotonic(), rehearsal=True,
                          run_dir=tmp_path)
    assert res["correct"] is False
    assert res["checks"]["warmup_unsteady"]["value"] == 1
    assert any(line.startswith("check warmup_unsteady")
               and line.endswith("FAILED") for line in lines)


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "florbench-100m.dense_record", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_without_a_tpu_fails_and_prints_nothing():
    p = _run_py(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_run_with_only_the_benchmark_files_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_names_resolve_to_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
    for m in bench["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
