"""Device-to-host copy rate of the record path: the wire bytes the
window's checkpoints gathered off the device (``transferred_bytes``) over
the seconds spent in the gathers and their copies (``copy_s``, the
``flor.ckpt.copy`` spans), in GB/s."""


def read(run):
    stats = [s for s in run.stats if s.get("copy_s") is not None]
    wire = sum(s.get("transferred_bytes") or 0 for s in stats)
    secs = sum(s["copy_s"] for s in stats)
    return wire / secs / 1e9 if wire and secs else None
