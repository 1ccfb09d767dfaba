"""Where each part of a cell lives, found by the names in BENCHMARK.json.

A configuration is ``configs[].file``; a traffic mix is
``bench/traffic/<traffic>.json``; a cell's correctness limits are
``bench/limits/<workload>.json``; a per-layer metric's reader is
``bench/metrics/<metric>.py``. Adding a configuration, a mix, a cell or a
metric adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


# every key a traffic mix may set: `why` says what it is for, and the
# generator reads the others
TRAFFIC_KEYS = {"why", "batch", "seq", "steps_per_ckpt", "trainable",
                "await_full", "max_warmup_intervals", "reference_block_rows"}


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def read_traffic(path: Path) -> dict:
    """A traffic mix, refused where it sets a key the generator does not
    read."""
    traffic = _read_json(path)
    unknown = set(traffic) - TRAFFIC_KEYS
    if unknown:
        raise SystemExit(f"bench: {path.name} sets {sorted(unknown)}, which "
                         f"no part of the benchmark reads")
    return traffic


class Cell:
    """One workload of BENCHMARK.json with its configuration, traffic mix,
    limits and metric entries resolved."""

    def __init__(self, name, config, traffic, limits, chips, end_to_end,
                 per_layer):
        self.name = name
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.chips = int(chips)
        self.end_to_end = end_to_end
        self.per_layer = per_layer

    @classmethod
    def find(cls, bench: dict, name: str) -> "Cell":
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"bench: no workload {name!r}; known: "
                             f"{sorted(cells)}")
        wl = cells[name]
        entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
        e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
        layer = [m for m in bench["per_layer"] if _applies(m, name)
                 and any(e["name"] == m["moves"] for e in e2e)]
        return cls(name, _read_json(ROOT / entry["file"]),
                   read_traffic(BENCH / "traffic" / f"{wl['traffic']}.json"),
                   _read_json(BENCH / "limits" / f"{name}.json"),
                   wl["chips"], e2e, layer)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_reader(metric_name: str):
    """The ``read(run)`` function of ``bench/metrics/<metric_name>.py``."""
    path = BENCH / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric_name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
