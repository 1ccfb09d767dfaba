"""Model FLOP/s utilization of the expert-specialised fine-tune with record
on: the FLOPs the window's steps require (the family's ``step_flops``:
forward everywhere, backward where gradients are needed, routed experts at
the expected rows, no recomputation), over the window's seconds, chips and
bf16 peak, in %. The formula of ``mfu.record``, in the cells whose steps
train a share of the experts."""


def read(run):
    w = run.window
    if not w.steps or not w.seconds:
        return None
    chips = run.cell.chips
    return 100.0 * run.flops_per_step * w.steps / w.seconds \
        / (chips * run.peaks["bf16_flops"])
