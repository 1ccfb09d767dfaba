"""Writer-queue wait per checkpoint: the seconds the training thread was
blocked on the writer's full queue after its submit (``queue_wait_s``,
the ``flor.ckpt.queue_wait`` span; ``ckpt_stall_ms`` stops before it),
averaged over the window's checkpoints, in ms."""


def read(run):
    waits = [s["queue_wait_s"] for s in run.stats
             if s.get("queue_wait_s") is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
