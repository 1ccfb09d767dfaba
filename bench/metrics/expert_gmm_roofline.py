"""Share of the bf16 peak that the grouped expert products reach in the
window, in %: the FLOPs the window's expert products require (the family's
``expert_flops`` per step: the forward at the expected routed rows, input
gradients where the backward needs them, weight gradients of the trained
experts only) over the summed device time of the kernels named
``expert_gmm*`` (forward and input gradients) and ``expert_tgmm*`` (weight
gradients). Work the step does beyond that, such as the forward again under
remat, frozen experts' weight gradients or the zero rows that pad each
expert's last row tile, reads as a lower share. A window whose count of
such kernels is not a whole multiple of its steps, or a family that gives
no ``expert_flops``, reads nothing."""

NAMES = ("expert_gmm", "expert_tgmm")


def read(run):
    flops = getattr(run.cell.family, "expert_flops", None)
    steps = run.window.steps
    runs = [(s, run.trace.op_count[n]) for n, s in run.trace.op_s.items()
            if n.split("/")[-1].startswith(NAMES)]
    n = sum(c for _, c in runs)
    if flops is None or not steps or not n or n % steps:
        return None
    t = run.cell.traffic
    need = steps * flops(run.cell.family.dims(run.cell.config), t["batch"],
                         t["seq"], t.get("trainable", "all"))
    return 100.0 * need / sum(s for s, _ in runs) / run.peaks["bf16_flops"]
