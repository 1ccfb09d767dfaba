"""The trace reduction, on a small trace recorded on a TPU v5e: three runs
of a 256x256 matmul program and one fused fingerprint+compare call."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import trace

XPLANE = Path(__file__).parent / "data" / "tiny_v5e.xplane.pb"


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (5.5, 5.7)]
    assert trace.union_length(iv) == 4
    assert trace.gaps(iv, 0, 8) == [(3, 5), (6, 8)]
    assert trace.gaps(iv, -1, 2.5) == [(-1, 0)]


def _device_events():
    import jax
    pd = jax.profiler.ProfileData.from_file(str(XPLANE))
    dev = next(p for p in pd.planes if p.name == "/device:TPU:0")
    lines = {ln.name: ln for ln in dev.lines}
    return ([(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in lines["XLA Ops"].events],
            [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in lines["XLA Modules"].events])


def test_reduction_matches_the_raw_events():
    ops, mods = _device_events()
    s = trace.reduce_trace(str(XPLANE))
    lo = min(o[1] for o in ops)
    hi = max(o[2] for o in ops)
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    # busy: the union of the op intervals, each overlap counted once
    want = trace.union_length([(a, b) for _, a, b in ops]) / 1e9
    assert s.busy_s == pytest.approx(want)
    assert 0 < s.busy_s <= s.window_s
    # per-op sums, named <program>/<op>
    kern = "fingerprint_and_changed/fingerprint_and_changed.1"
    assert s.op_count[kern] == 1
    raw = [b - a for n, a, b in ops if n.startswith("%fingerprint_and_changed")]
    assert s.op_s[kern] == pytest.approx(sum(raw) / 1e9)
    assert sum(s.op_s.values()) == pytest.approx(
        sum(b - a for _, a, b in ops) / 1e9)
    lam = [n for n in s.module_count if n.startswith("jit__lambda")]
    assert len(lam) == 1 and s.module_count[lam[0]] == 3
    assert sum(s.module_count.values()) == len(mods)
    # idle gaps cover the rest of the window
    idle = sum(v for _, v in s.idle_gaps)
    assert idle == pytest.approx(s.window_s - s.busy_s, rel=1e-6)


def _reader(name):
    from harness.spec import BENCH
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_roofline_reader_on_the_recorded_kernel():
    s = trace.reduce_trace(str(XPLANE))
    leaf = ((64 * 16384 // 4, 4), "float32")       # the traced 4 MiB leaf
    from harness import work
    run = SimpleNamespace(
        trace=s, window=SimpleNamespace(keys=["k"]),
        fingerprint_leaves=[leaf],
        fingerprint_bytes_per_ckpt=work.fingerprint_bytes([leaf]),
        peaks={"hbm_bytes_per_s": 819e9})
    share = _reader("fingerprint_roofline")(run)
    kern = s.op_s["fingerprint_and_changed/fingerprint_and_changed.1"]
    assert share == pytest.approx(
        100 * work.fingerprint_bytes([leaf]) / 819e9 / kern)
    assert 0 < share <= 105
    # a count that is not one run per leaf per checkpoint reads nothing
    run.window.keys = ["k", "k2"]
    assert _reader("fingerprint_roofline")(run) is None


def test_idle_reader():
    t = SimpleNamespace(busy_s=1.0, window_s=4.0)
    assert _reader("device_idle.record")(SimpleNamespace(trace=t)) == 75.0
