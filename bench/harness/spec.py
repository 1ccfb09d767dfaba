"""Where each part of a cell lives, found by the names in BENCHMARK.json.

A configuration is ``configs[].file``; a traffic mix is
``bench/traffic/<traffic>.json``; a cell's correctness limits are
``bench/limits/<workload>.json``; a per-layer metric's reader is
``bench/metrics/<metric>.py``. A configuration's ``reference`` key, ``<ref>``,
names its model family: ``bench/families/<ref>.py`` builds the program's
model (its docstring gives the interface) and ``bench/references/<ref>.py``
is the plain reference. Adding a configuration, a model family, a mix, a
cell or a metric adds files and entries and edits none.
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


# every key a traffic mix may set, beyond those its cell's family reads:
# `why` says what it is for, and the generator reads the others
TRAFFIC_KEYS = {"why", "batch", "seq", "steps_per_ckpt", "trainable",
                "await_full", "max_warmup_intervals", "reference_block_rows"}


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by its path."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_parts(config: dict):
    """(family, reference): the modules that the configuration's
    ``reference`` key names. A configuration that names none, or a name
    without both files, is refused before either is loaded."""
    ref = config.get("reference")
    known = sorted(p.stem for p in (BENCH / "families").glob("*.py")
                   if (BENCH / "references" / p.name).is_file())
    if ref not in known:
        raise SystemExit(f"bench: configuration {config.get('name')!r} names "
                         f"the reference {ref!r}; known: {known}")
    return load_module("families", ref), load_module("references", ref)


def check_traffic(name: str, traffic: dict, family) -> None:
    """Refuse a traffic mix that sets a key neither the generator nor the
    cell's family reads."""
    unknown = (set(traffic) - TRAFFIC_KEYS
               - set(getattr(family, "TRAFFIC_KEYS", ())))
    if unknown:
        raise SystemExit(f"bench: the traffic mix of {name} sets "
                         f"{sorted(unknown)}, which no part of the benchmark "
                         f"reads")


class Cell:
    """One workload of BENCHMARK.json with its configuration, model family,
    plain reference, traffic mix, limits and metric entries resolved."""

    def __init__(self, name, config, traffic, limits, chips, end_to_end,
                 per_layer):
        self.family, self.reference = model_parts(config)
        check_traffic(name, traffic, self.family)
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
                   _read_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
                   _read_json(BENCH / "limits" / f"{name}.json"),
                   wl["chips"], e2e, layer)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_reader(metric_name: str):
    """The ``read(run)`` function of ``bench/metrics/<metric_name>.py``."""
    return load_module("metrics", metric_name).read
