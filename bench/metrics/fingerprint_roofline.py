"""Share of its HBM roofline that the fused fingerprint+compare kernel
reaches in the window, in %: the least time the pass could take (the bytes
it must move, from the leaves' shapes, over the chip's HBM bandwidth) over
the kernel's summed device time. One kernel run per leaf per checkpoint;
a window whose trace holds another count reads nothing."""

# the names the kernel's op goes by in the trace (``<program>/<op>``): the
# jitted wrapper's custom call today, and the kernel's own name once named
NAMES = ("fingerprint_and_changed", "fingerprint_changed", "fp_changed")


def read(run):
    runs = [(s, run.trace.op_count[n]) for n, s in run.trace.op_s.items()
            if n.split("/")[-1].startswith(NAMES)]
    n = sum(c for _, c in runs)
    ckpts = len(run.window.keys)
    leaves = sum(1 for shape, _ in run.fingerprint_leaves
                 if all(shape) or not shape)
    if not n or n != ckpts * leaves:
        return None
    least = ckpts * run.fingerprint_bytes_per_ckpt \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / sum(s for s, _ in runs)
