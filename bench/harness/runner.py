"""One run of one cell, from set-up to the line of results."""
from __future__ import annotations

import math
import shutil
import sys
import time

from harness import checks, work
from harness.spec import ROOT, load_reader

CACHE_DIR = ROOT / ".jax_cache"
RUNS_DIR = ROOT / ".bench_runs"
TRACE_DIR = ROOT / ".bench_traces"


def use_compile_cache():
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, holding every program, so only a cell's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def window_stored_bytes(store, window) -> int:
    """On-disk bytes of the chunk files that the window's checkpoints added
    to the store: the chunks their manifests name that the checkpoint before
    the window did not already reach, each file's size read once."""
    before = store.closure_chunks([window.last_warmup_key])
    named = set()
    for key in window.keys:
        for leaf in store.get_manifest(key)["leaves"]:
            named.update(leaf.get("chunks") or leaf.get("delta", {}).values())
    return store.chunk_bytes(named - before - {None})


class RunView:
    """What a per-layer metric's reader may read: the window, the record
    pipeline's per-checkpoint stats, the trace summary and the work the
    algorithm must do. A reader returns None when it finds nothing."""

    def __init__(self, cell, rec, summary, peaks, flops_per_step,
                 fp_bytes_per_ckpt, fp_leaves, step_name):
        self.cell = cell
        self.window = rec.window
        self.stats = rec.stats
        self.trace = summary
        self.peaks = peaks
        self.flops_per_step = flops_per_step
        self.fingerprint_bytes_per_ckpt = fp_bytes_per_ckpt
        self.fingerprint_leaves = fp_leaves
        self.step_name = step_name


def _device(jax, peak):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run_cell(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
             fault=None, control: bool = False, rehearsal: bool = False,
             run_dir=None):
    """Run ``cell`` once. Returns (result dict, check lines for stderr).

    The run's store is ``run_dir``, by default ``<checkout>/.bench_runs/
    <cell>``; it is removed before the function returns.

    ``rehearsal`` runs the same path off the chip (tests): no device metric
    is reported. ``fault`` (tests) replaces the window's step:
    ``{"step": lambda system: step_fn}``. ``control`` puts the readings of
    the float8 reference in the program's place, from the same weights and
    tokens, so that the comparison judges the control."""
    import jax
    if not rehearsal:
        use_compile_cache()
    from harness import model as M
    from harness.peaks import peaks as peak_table
    from harness.record import CompileCounter, Recorder
    from harness.trace import find_xplane, reduce_trace, top, merged_gaps

    compiles = CompileCounter()
    dims = cell.family.dims(cell.config)
    kind = jax.devices()[0].device_kind
    peaks = None if rehearsal else peak_table(kind)
    system = M.System(cell.family, cell.config, cell.traffic)
    run_dir = RUNS_DIR / cell.name if run_dir is None else run_dir
    trace_dir = TRACE_DIR / cell.name
    shutil.rmtree(trace_dir, ignore_errors=True)
    t_setup = {}
    tracing = trace and not rehearsal

    def window_open():
        if tracing:
            # host spans (the benchmark's and the runtime's) without the
            # Python tracer, whose per-call events would slow the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    def window_close():
        if tracing:
            jax.profiler.stop_trace()

    rec = Recorder(system, cell.traffic, seed, run_dir, compiles, fault=fault)
    try:
        rec.run(seconds, on_window_open=window_open,
                on_window_close=window_close,
                setup_done=lambda: t_setup.setdefault(
                    "s", time.monotonic() - t_start))
        # ---- the store: the chosen window checkpoint, read back
        from repro.checkpoint.store import CheckpointStore
        stored = window_stored_bytes(CheckpointStore(rec.store_root),
                                     rec.window)
        mismatch = checks.store_mismatch(checks.flat_paths(rec.check_state),
                                         rec.restored)
        rec.check_state = rec.restored = None
        phases = {"setup_s": t_setup["s"], "window_s": rec.window.seconds,
                  "drain_s": rec.drain_s, "restore_check_s": rec.restore_s}
        summary = None
        if tracing:
            t0 = time.monotonic()
            summary = reduce_trace(find_xplane(str(trace_dir)))
            phases["trace_read_s"] = time.monotonic() - t0
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the reference follows the run's first steps
    t0 = time.monotonic()
    state = system.init_state(M.seed_key(seed))
    frozen = state["frozen"]
    train = state["train"].params
    del state
    opt = {"peak_lr": M.PEAK_LR, "warmup": M.WARMUP, "b1": M.B1, "b2": M.B2,
           "eps": M.ADAM_EPS, "weight_decay": M.WEIGHT_DECAY,
           "grad_clip": M.GRAD_CLIP}
    rows = int(cell.traffic["reference_block_rows"])
    ref = cell.reference.first_steps(dims, opt, frozen, train,
                                     rec.captured.batches, rows)
    got = cell.reference.first_steps(dims, opt, frozen, train,
                                     rec.captured.batches, rows,
                                     precision="fp8") \
        if control else checks.program_readings(rec.captured)
    del frozen, train
    phases["reference_s"] = time.monotonic() - t0
    numbers = checks.step_numbers(got, ref)
    w = rec.window
    numbers.update(store_mismatch=mismatch, window_compiles=w.compiles,
                   warmup_unsteady=int(not w.steady))
    ok, check, lines = checks.judge(numbers, {**cell.limits,
                                              **checks.WINDOW_LIMITS})
    ok = ok and math.isfinite(rec.final_loss)
    result = {"correct": ok, "attempted": w.steps,
              "failed": 0 if math.isfinite(rec.final_loss) else w.steps,
              "metrics": {}, "device": _device(jax, rec.memory_peak)}
    if not rehearsal:
        if not trace:
            values = {
                "record_tokens_per_s": w.tokens / w.seconds,
                "stored_mb_per_ckpt": stored / len(rec.window.keys) / 1e6,
                "setup_s": t_setup["s"],
            }
            for m in cell.end_to_end:
                result["metrics"][m["name"]] = {"value": values[m["name"]],
                                                "unit": m["unit"]}
        else:
            leaves = [(sd.shape, sd.dtype) for sd in
                      jax.tree_util.tree_leaves(system.state_shapes)]
            view = RunView(
                cell, rec, summary, peaks,
                cell.family.step_flops(dims, rec.batch, rec.seq,
                                       system.trainable),
                work.fingerprint_bytes(leaves), leaves,
                M.STEP_NAME)
            for m in cell.per_layer:
                v = load_reader(m["name"])(view)
                if v is not None:
                    result["metrics"][m["name"]] = {"value": v,
                                                    "unit": m["unit"]}
            result["device"]["busy_s"] = summary.busy_s
            result["device"]["window_s"] = summary.window_s
            result["breakdown"] = {
                "device_ops": top(summary.op_s),
                "idle_gaps": merged_gaps(summary.idle_gaps)}
    print("bench: phases " + " ".join(f"{k} {v:.3f}" for k, v in
                                      phases.items()), file=sys.stderr)
    result["phases"] = phases
    result["window"] = {"seconds": w.seconds, "steps": w.steps,
                        "checkpoints": len(w.keys), "compiles": w.compiles,
                        "stored_bytes": stored,
                        "new_bytes_counted": sum(s["new_bytes"]
                                                 for s in rec.stats),
                        "warmup_intervals": w.warmup_intervals,
                        "steady": w.steady}
    result["checks"] = check
    return result, lines
