"""Device idle share of the traced window: 1 - (union of the intervals in
which an operation ran on the device) / (the window), in %."""


def read(run):
    t = run.trace
    if not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
