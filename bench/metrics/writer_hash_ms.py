"""Writer-thread hash time per checkpoint: the seconds the writer's job spent
in blake2b over each chunk (``hash_s``, counted by the store), averaged over
the window's checkpoints, in ms."""


def read(run):
    secs = [s["hash_s"] for s in run.stats if s.get("hash_s") is not None]
    return 1e3 * sum(secs) / len(secs) if secs else None
