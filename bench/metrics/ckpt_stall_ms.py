"""Foreground stall per checkpoint: the record pipeline's own
``submit_stall_s`` (fingerprint, mask sync, gather and device-to-host copy
on the training thread), averaged over the window's checkpoints, in ms."""


def read(run):
    stalls = [s["submit_stall_s"] for s in run.stats
              if s.get("submit_stall_s") is not None]
    return 1e3 * sum(stalls) / len(stalls) if stalls else None
