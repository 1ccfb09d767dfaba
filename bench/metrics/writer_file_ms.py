"""Writer-thread file time per checkpoint: the seconds the writer's job spent
in the existence check, directory creation, tmp write and rename of each
chunk, and the manifest write (``file_s``, counted by the store), averaged
over the window's checkpoints, in ms."""


def read(run):
    secs = [s["file_s"] for s in run.stats if s.get("file_s") is not None]
    return 1e3 * sum(secs) / len(secs) if secs else None
