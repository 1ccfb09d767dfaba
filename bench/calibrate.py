#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload florbench-100m.dense_record \\
        --seeds 101-112 --control-seeds 3 --out calib.json

In one process, at the cell's own sizes, through the window's own step:

* ``program``: the program's first steps against the plain reference, on
  each seed (the lower readings);
* ``control``: the reference computed with float8 operands put in the
  program's place (the precision step below the configuration's bfloat16),
  on the first ``--control-seeds`` seeds;
* ``half_batch``: the program's step fed half of each batch, the mean taken
  over the rest, on the same seeds. A step that returns its state unchanged
  reads 1 on ``update_gap`` by construction and needs no run.

Each reading also carries ``correct``: the verdict of the harness's own
comparison under the cell's limits (``bench/limits/<cell>.json``). Prints
one JSON line per reading and writes them all to ``--out``. Like
``run.py``, it refuses to run without the cell's TPU chips.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def seeds_arg(text: str) -> list:
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def program_first_steps(system, traffic, seed, half_batch=False):
    """The run's first steps outside a session: same step, same feed."""
    import jax.numpy as jnp
    from harness import data, model
    from harness.record import CHECK_STEPS, Captured, _host
    b, s = int(traffic["batch"]), int(traffic["seq"])
    state = system.init_state(model.seed_key(seed))
    c = Captured(p0=_host(state["train"].params))
    for g in range(CHECK_STEPS):
        toks = data.tokens(g, seed, b, s, system.cfg.vocab_size)
        c.batches.append(toks)
        fed = toks[: b // 2] if half_batch else toks
        state, m = system.step(state, {"tokens": jnp.asarray(fed)})
        c.losses.append(float(m["loss"]))
        if g == 0:
            c.mu1 = _host(state["train"].mu)
    c.p3 = _host(state["train"].params)
    return c


def readings(cell, seeds, control_seeds, emit=print):
    """Every reading of one cell, as dicts; ``emit`` gets each as made."""
    from harness import checks, model
    dims = cell.family.dims(cell.config)
    system = model.System(cell.family, cell.config, cell.traffic)
    opt = {"peak_lr": model.PEAK_LR, "warmup": model.WARMUP, "b1": model.B1,
           "b2": model.B2, "eps": model.ADAM_EPS,
           "weight_decay": model.WEIGHT_DECAY, "grad_clip": model.GRAD_CLIP}
    rows = int(cell.traffic["reference_block_rows"])
    out = []

    def record(kind, seed, numbers):
        limits = {k: cell.limits[k] for k in numbers}
        row = {"kind": kind, "seed": seed, **numbers,
               "correct": checks.judge(numbers, limits)[0]}
        out.append(row)
        emit(row)

    for i, seed in enumerate(seeds):
        cap = program_first_steps(system, cell.traffic, seed)
        state = system.init_state(model.seed_key(seed))
        frozen, train = state["frozen"], state["train"].params
        del state
        ref = cell.reference.first_steps(dims, opt, frozen, train,
                                         cap.batches, rows)
        record("program", seed, checks.step_numbers(
            checks.program_readings(cap), ref))
        if i < control_seeds:
            ctl = cell.reference.first_steps(dims, opt, frozen, train,
                                             cap.batches, rows,
                                             precision="fp8")
            record("control", seed, checks.step_numbers(ctl, ref))
            half = program_first_steps(system, cell.traffic, seed,
                                       half_batch=True)
            record("half_batch", seed, checks.step_numbers(
                checks.program_readings(half), ref))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from harness.runner import use_compile_cache
    from harness.spec import Cell, load_benchmark
    cell = Cell.find(load_benchmark(), args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"calibrate: {cell.name} needs {cell.chips} TPU chip(s)",
              file=sys.stderr)
        return 2
    use_compile_cache()
    rows = readings(cell, args.seeds, args.control_seeds,
                    emit=lambda r: print(json.dumps(r), flush=True))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(
        {"workload": cell.name, "device": devs[0].device_kind,
         "readings": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
