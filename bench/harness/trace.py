"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, time
per kernel and per jitted program, and the idle gaps with what the host did
in each.

Device planes are named ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per operation and ``XLA Modules`` one per run of a jitted
program. Busy time is the union of the operation intervals, so overlapping
operations count once. The host's events (planes ``/host:*``) share the
device planes' clock; the benchmark's own spans (``bench.*``) are among
them: ``bench.window`` bounds the measured window, and ``bench.feed``,
``bench.step`` and ``bench.checkpoint`` say what the training thread did.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ATTRIBUTED_GAPS = 200         # the longest idle gaps get a host activity


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float):
    """``(start, end)`` stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in sorted(intervals):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


@dataclass
class Summary:
    """What a trace says about one window, in seconds."""
    window_s: float
    busy_s: float                       # mean over the devices traced
    devices: int
    op_s: dict = field(default_factory=dict)      # op name -> seconds
    op_count: dict = field(default_factory=dict)
    module_s: dict = field(default_factory=dict)  # jitted program -> seconds
    module_count: dict = field(default_factory=dict)
    idle_gaps: list = field(default_factory=list)  # [(host activity, s)]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_label(module: str, op: str) -> str:
    """``<program>/<op>`` from a module event (``jit_<fn>(<id>)``) and an op
    event (``%<op> = <HLO text>``)."""
    prog = module.split("(")[0]
    prog = prog[4:] if prog.startswith("jit_") else prog
    return f"{prog}/{op.split(' = ')[0].lstrip('%')}"


def reduce_trace(path: str, window=None) -> Summary:
    """Summarize the device activity inside ``window`` (``(start_ns,
    end_ns)`` on the trace's clock; default: the ``bench.window`` span, else
    the extent of the device events). Events are clipped to the window.
    Ops are named ``<program>/<op>`` by the program run they fall in; idle
    gaps by what the thread that opened the window was doing."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host_lines = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host_lines.extend(list(_events(line)) for line in plane.lines)
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}* plane")
    # the window's own thread: the host line that holds the window span
    main = next((evs for evs in host_lines
                 if any(n == WINDOW_SPAN for n, _, _ in evs)), [])
    if window is None:
        spans = [(s, e) for n, s, e in main if n == WINDOW_SPAN]
        if spans:
            window = max(spans, key=lambda se: se[1] - se[0])
    per_dev = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        ops = list(_events(lines[OPS_LINE])) if OPS_LINE in lines else []
        mods = list(_events(lines[MODULES_LINE])) \
            if MODULES_LINE in lines else []
        per_dev.append((ops, sorted(mods, key=lambda m: m[1])))
    if window is None:
        starts = [s for ops, _ in per_dev for _, s, _ in ops]
        ends = [e for ops, _ in per_dev for _, _, e in ops]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    lo, hi = window

    def clip(evs):
        return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                if e > lo and s < hi]

    out = Summary(window_s=(hi - lo) / 1e9, busy_s=0.0, devices=len(devices))
    busy, first_ops = [], None
    for ops, mods in per_dev:
        starts = [m[1] for m in mods]
        named = []
        for n, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and mods[i][2] >= s else "?"
            named.append((op_label(mod, n), s, e))
        named, mods = clip(named), clip(mods)
        busy.append(union_length([(s, e) for _, s, e in named]) / 1e9)
        for table, counts, evs in ((out.op_s, out.op_count, named),
                                   (out.module_s, out.module_count, mods)):
            for n, s, e in evs:
                table[n] = table.get(n, 0.0) + (e - s) / 1e9
                counts[n] = counts.get(n, 0) + 1
        if first_ops is None:
            first_ops = named
    # the tables sum over devices; busy time is a per-device mean
    out.busy_s = sum(busy) / len(busy)
    host_in = clip([h for h in main if h[0] != WINDOW_SPAN])
    idle = sorted(gaps([(s, e) for _, s, e in first_ops], lo, hi),
                  key=lambda g: g[0] - g[1])
    # name the longest gaps by what the host did; pool the many short ones
    for s, e in idle[:ATTRIBUTED_GAPS]:
        out.idle_gaps.append((_host_activity(host_in, s, e), (e - s) / 1e9))
    rest = sum(e - s for s, e in idle[ATTRIBUTED_GAPS:]) / 1e9
    if rest:
        out.idle_gaps.append(("shorter gaps", rest))
    return out


def _host_activity(host, s: float, e: float) -> str:
    """The innermost host event covering the middle of [s, e], or the one
    that overlaps the stretch most; ``idle host`` when none does."""
    mid = (s + e) / 2
    covering = [(he - hs, n) for n, hs, he in host if hs <= mid <= he]
    if covering:
        return min(covering)[1]
    overlap = [(min(e, he) - max(s, hs), n) for n, hs, he in host
               if he > s and hs < e]
    return max(overlap)[1] if overlap else "idle host"


def top(table: dict, n: int = 10):
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def merged_gaps(idle_gaps, n: int = 10):
    """The ``n`` host activities under which the device idled longest,
    each with the summed seconds of its gaps."""
    by = {}
    for name, s in idle_gaps:
        by[name] = by.get(name, 0.0) + s
    return top(by, n)
