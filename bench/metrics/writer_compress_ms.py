"""Writer-thread compress time per checkpoint: the seconds the writer's job
spent in the store's codec, zstd where the environment has it, else zlib
(``compress_s``, counted by the store), averaged over the window's
checkpoints, in ms."""


def read(run):
    secs = [s["compress_s"] for s in run.stats
            if s.get("compress_s") is not None]
    return 1e3 * sum(secs) / len(secs) if secs else None
