#!/usr/bin/env python3
"""Compile each cell's jitted step and record kernels at full size for a
described TPU v5e, with no chip: what the chip's compiler would refuse, and
how much device memory each program needs.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload NAME ...]

Prints one JSON line per program with ``memory_analysis()``'s sizes. A
compile that passes is not a chip run: it says nothing about time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}


def rehearse(cell, device) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from harness import model
    from repro.kernels import ops
    from repro.kernels.chunk_delta import fingerprint_changed_pallas

    one = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    system = model.System(cell.family, cell.config, cell.traffic)
    b, s = int(cell.traffic["batch"]), int(cell.traffic["seq"])
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one)}
    out = []
    step = system.lower_step(on_chip(system.state_shapes), batch).compile()
    out.append({"workload": cell.name, "program": model.STEP_NAME,
                "memory": _memory(step),
                "mosaic": "tpu_custom_call" in step.as_text()})
    cw = 16384

    def fused(x, prev):
        return fingerprint_changed_pallas(ops._as_u32_blocks(x, cw), prev,
                                          interpret=False)
    shapes = {(sd.shape, str(sd.dtype)) for sd in
              jax.tree_util.tree_leaves(system.state_shapes)}
    for shape, dtype in sorted(shapes):
        x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one)
        g = jax.eval_shape(lambda x: ops._as_u32_blocks(x, cw), x).shape[0]
        prev = jax.ShapeDtypeStruct((g, 2), jnp.uint32, sharding=one)
        k = jax.jit(fused).lower(x, prev).compile()
        out.append({"workload": cell.name, "program": "fingerprint_changed",
                    "leaf": [list(shape), dtype], "memory": _memory(k),
                    "mosaic": "tpu_custom_call" in k.as_text()})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from harness.spec import Cell, load_benchmark
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = load_benchmark()
    names = args.workload or [w["name"] for w in bench["workloads"]]
    for name in names:
        for row in rehearse(Cell.find(bench, name), topo.devices[0]):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
