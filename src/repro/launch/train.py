"""Training driver with Flor record integrated as a first-class feature.

    PYTHONPATH=src python -m repro.launch.train --arch florbench-100m \
        --smoke --epochs 4 --steps-per-epoch 8 --run-dir /tmp/run1

Fault tolerance IS the paper's substrate: on start, if the run dir already
holds checkpoints, training resumes from the latest epoch checkpoint
(weak-init replay of the remainder). Kill the process mid-run and relaunch
with the same command to see it.

Run lineage (continuous training): record several runs into one shared
store and chain them —

    ... train --run-dir /tmp/base --store-root /tmp/store --run-id base
    ... train --run-dir /tmp/ft1  --store-root /tmp/store --run-id ft1 \
        --parent-run base          # warm-starts; 1st ckpt is a cross-run delta

Inspect/reclaim with `python -m repro.launch.runs list|show|gc|rm`.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def _done_epochs(store) -> set:
    """Epochs with a complete checkpoint in ``store``. Shard MEMBER
    manifests (<key>.shard<h>) and checkpoints a distributed record marked
    incomplete never anchor a resume — only stitched (or flat) epoch keys
    count as done."""
    from repro.checkpoint.store import _safe
    inc = {_safe(k) for k in
           (store.get_meta("incomplete_ckpts") or {}).get("keys") or ()}
    done = set()
    for k in store.list_keys():
        if "_at_" in k and ".shard" not in k and k not in inc:
            try:
                done.add(int(k.split("_at_")[1].split(".")[0]))
            except ValueError:
                pass
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="florbench-100m")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config (CPU-runnable)")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--steps-per-epoch", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--run-dir", required=True)
    ap.add_argument("--epsilon", type=float, default=1.0 / 15)
    ap.add_argument("--no-adaptive", action="store_true")
    ap.add_argument("--no-flor", action="store_true",
                    help="vanilla baseline (no record) for overhead benchs")
    ap.add_argument("--sync-log", action="store_true",
                    help="legacy synchronous flor.log (serialize + write on "
                         "the step path) instead of the background log "
                         "stage; for overhead comparisons")
    ap.add_argument("--log-spill-bytes", type=int, default=1 << 20,
                    help="spill logged arrays larger than this many host "
                         "bytes to the checkpoint store, logging a ref row "
                         "(0 disables)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 1x1; data x model over local devices "
                         "(GLOBAL devices under --num-processes > 1)")
    ap.add_argument("--coordinator", default="127.0.0.1:12355",
                    help="jax.distributed coordinator address "
                         "(host:port) for true multi-process record")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this process's id in the record fleet")
    ap.add_argument("--num-processes", type=int, default=1,
                    help="record fleet size; > 1 turns on distributed "
                         "record: each process checkpoints only its local "
                         "shards, process 0 stitches the v4 manifests")
    ap.add_argument("--stitch-timeout", type=float, default=30.0,
                    help="seconds the stitch rendezvous waits for every "
                         "host before marking a checkpoint incomplete")
    ap.add_argument("--ckpt-shard-axes", default="",
                    help="comma-separated mesh axes mapping onto store "
                         "shards (default: all axes — one shard/device)")
    ap.add_argument("--store-root", default=None,
                    help="SHARED checkpoint store root (multi-run lineage); "
                         "default: private <run-dir>/store")
    ap.add_argument("--run-id", default=None,
                    help="explicit run id in the shared store")
    ap.add_argument("--parent-run", default=None,
                    help="ancestor run id: warm-start from its final "
                         "checkpoint and record cross-run deltas")
    args = ap.parse_args()

    import repro.configs as C
    import repro.flor as flor
    from repro.data import synthetic_batch
    from repro.launch.compile_cache import CompileClock, use_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.parallel import use_mesh
    from repro.train.step import build_train_step

    use_compile_cache()
    clock = CompileClock()

    # true multi-process record: join the fleet BEFORE any jax call touches
    # the backend, so jax.devices() spans every host
    group = None
    if args.num_processes > 1:
        from repro.parallel.rendezvous import init_distributed
        group = init_distributed(args.coordinator, args.process_id,
                                 args.num_processes)
        print(f"distributed record: process {group.process_id}/"
              f"{group.num_processes}, {jax.local_device_count()} local / "
              f"{jax.device_count()} global devices", flush=True)

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    init_state, train_step = build_train_step(cfg)
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    if group is not None and mesh is None:
        ap.error("--num-processes > 1 requires --mesh (the global device "
                 "mesh spanning every process)")
    shard_axes = tuple(a for a in args.ckpt_shard_axes.split(",") if a)

    key = jax.random.PRNGKey(args.seed)
    with use_mesh(mesh):
        if mesh is None:
            ts = jax.jit(train_step)
            state = jax.jit(init_state)(key)
        else:
            # the state lives sharded over the mesh and keeps its layout
            # from step to step, so every checkpoint shards the same way
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.launch.specs import state_shardings
            st_sh = state_shardings(cfg, mesh,
                                    jax.eval_shape(init_state, key))
            ts = jax.jit(train_step,
                         out_shardings=(st_sh, NamedSharding(mesh, P())))
            state = jax.jit(init_state, out_shardings=st_sh)(key)

        if args.no_flor:
            t0 = time.time()
            for epoch in range(args.epochs):
                for s in range(args.steps_per_epoch):
                    b = synthetic_batch(cfg, args.batch, args.seq,
                                        epoch * args.steps_per_epoch + s,
                                        args.seed)
                    state, m = ts(state, b)
                jax.block_until_ready(m["loss"])
                print(f"epoch {epoch} loss {float(m['loss']):.4f}", flush=True)
            print(f"vanilla wall {time.time() - t0:.2f}s")
            print(clock, flush=True)
            return

        with flor.Session(
                args.run_dir, mode="record",
                record=flor.RecordSpec(epsilon=args.epsilon,
                                       adaptive=not args.no_adaptive,
                                       async_log=not args.sync_log,
                                       log_spill_bytes=args.log_spill_bytes,
                                       # with a mesh: each device checkpoints
                                       # its own shards (v4 manifests);
                                       # distributed: per-process local
                                       # shards, lead-stitched v4s
                                       mesh=mesh,
                                       ckpt_shard_axes=shard_axes,
                                       distributed=group or False,
                                       stitch_timeout_s=args.stitch_timeout),
                lineage=flor.LineageSpec(store_root=args.store_root,
                                         run_id=args.run_id,
                                         parent_run=args.parent_run)) as sess:
            ctx = sess.ctx
            if ctx.parent_run and not ctx.store.list_keys():
                # derived run (fine-tune of a fine-tune): start from the
                # ancestor's final state; the first checkpoint is already a
                # cross-run delta against it
                print(f"warm start from run {ctx.parent_run!r}", flush=True)
                state = sess.warm_start("train", like=state)
                state = jax.tree_util.tree_map(jnp.asarray, state)
            # crash-restart: resume from the latest epoch checkpoint if any
            done = _done_epochs(ctx.store)
            resume_from = max(done) + 1 if done else 0
            if resume_from:
                # physical restore of the latest Loop End Checkpoint, then
                # skip the completed epochs — restart == weak-init replay
                print(f"resuming: restoring epoch {max(done)} checkpoint",
                      flush=True)
                state = ctx.store.get_tree(f"train@{max(done)}.0", like=state)

            t0 = time.time()
            steps = sess.arg("steps_per_epoch", args.steps_per_epoch)
            with sess.checkpointing(state=state) as ckpt:
                for epoch in sess.loop("epochs",
                                       range(sess.arg("epochs", args.epochs))):
                    if epoch < resume_from:
                        continue
                    for s in sess.loop("train", range(steps)):
                        b = synthetic_batch(cfg, args.batch, args.seq,
                                            epoch * steps + s, args.seed)
                        ckpt.state, m = ts(ckpt.state, b)
                    flor.log("loss", m["loss"])
                    print(f"epoch {epoch} done loss {float(m['loss']):.6f}",
                          flush=True)
            state = ckpt.state
        print(f"record wall {time.time() - t0:.2f}s")
        print("checkpoints: " + ",".join(
            str(e) for e in sorted(_done_epochs(ctx.store))), flush=True)
        print(clock, flush=True)


if __name__ == "__main__":
    main()
