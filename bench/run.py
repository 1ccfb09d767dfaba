#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip this process finds.

    python3 bench/run.py --workload florbench-100m.dense_record \\
        --seed 7 --seconds 10 --trace 0

Everything a cell needs is found by the names in BENCHMARK.json: its
configuration file, its traffic mix (``bench/traffic/<mix>.json``), its
correctness limits (``bench/limits/<cell>.json``) and, with ``--trace 1``,
the reader of each per-layer metric (``bench/metrics/<metric>.py``).

The run makes the weights and tokens from ``--seed``, warms the record path
up to its steady state (set-up), measures whole checkpoint intervals for at
least ``--seconds`` seconds, then compares what the window produced with
the plain reference. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``), then ``checks``: each number compared, with
its limit. ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of the window.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. The compile cache is ``<checkout>/.jax_cache``
and the run's store ``<checkout>/.bench_runs/<cell>``, removed at exit.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from harness.spec import Cell, load_benchmark
    cell = Cell.find(load_benchmark(), args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 2
    from harness.runner import run_cell
    result, check_lines = run_cell(cell, args.seed, args.seconds,
                                   bool(args.trace), t_start=T_START)
    for line in check_lines:
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
