"""Writer-thread throughput: the wire bytes the window's checkpoints handed
to the store (``stored_bytes``), over the seconds the writer spent on their
jobs (``materialize_s``: hash, compress, write, manifest), in MB/s."""


def read(run):
    wire = sum(s.get("stored_bytes") or 0 for s in run.stats)
    secs = sum(s.get("materialize_s") or 0.0 for s in run.stats)
    return wire / secs / 1e6 if wire and secs else None
