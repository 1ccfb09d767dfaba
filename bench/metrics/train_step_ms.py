"""Device time per training step: the summed device time of the jitted
step's program runs in the traced window, over their count, in ms."""


def read(run):
    runs = [(s, run.trace.module_count[n]) for n, s in run.trace.module_s.items()
            if run.step_name in n]
    n = sum(c for _, c in runs)
    return 1e3 * sum(s for s, _ in runs) / n if n else None
