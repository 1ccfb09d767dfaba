"""Writer-thread files per checkpoint: the pack and manifest files the
writer's job published (``files_written``, counted by the store), averaged
over the window's checkpoints."""


def read(run):
    files = [s["files_written"] for s in run.stats
             if s.get("files_written") is not None]
    return sum(files) / len(files) if files else None
