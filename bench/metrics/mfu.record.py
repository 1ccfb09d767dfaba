"""Model FLOP/s utilization with record on: the FLOPs the window's steps
require (forward everywhere, backward where gradients are needed, no
recomputation), over the window's seconds, chips and bf16 peak, in %."""


def read(run):
    w = run.window
    if not w.steps or not w.seconds:
        return None
    chips = run.cell.chips
    return 100.0 * run.flops_per_step * w.steps / w.seconds \
        / (chips * run.peaks["bf16_flops"])
