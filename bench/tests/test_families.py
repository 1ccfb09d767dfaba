"""A configuration's model family and plain reference are found by the
``reference`` key of its file: the benchmark's cells read what they read
before the lookup existed, a configuration with no known family is refused,
a traffic key passes only where the family reads it, and a family added as
new files runs through ``run_cell`` with no file of the benchmark edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest
from tiny import TINY_CONFIG, tiny_cell

from harness import model as M
from harness import spec
from harness.spec import BENCH, ROOT, Cell, load_benchmark

# pinned from the dense formula and the dense ModelConfig as they were
# before the family modules existed (CPU)
FLOPS_PER_STEP = {"florbench-100m.dense_record": 23499041144832.0,
                  "granite-3-2b.frozen_ft_record": 27691801444352.0}
PROGRAM_CONFIGS = {
    "florbench-100m.dense_record": dict(
        name="florbench-100m", family="dense", num_layers=12, d_model=768,
        num_heads=12, num_kv_heads=12, d_ff=3072, vocab_size=32768,
        head_dim=64, ffn_activation="gelu", rope_theta=10000.0,
        norm_eps=1e-5, tie_embeddings=True),
    "granite-3-2b.frozen_ft_record": dict(
        name="granite-3-2b", family="dense", num_layers=8, d_model=2048,
        num_heads=32, num_kv_heads=8, d_ff=8192, vocab_size=49155,
        head_dim=64, ffn_activation="swiglu", rope_theta=10000.0,
        norm_eps=1e-5, tie_embeddings=True),
}
# sha256 over (path, dtype, bytes) of every leaf of the tiny cell's initial
# state from seed 2**31 + 5, pinned the same way (CPU)
STATE_DIGESTS = {
    "all": "951ee5a195230165f9d0752ca297cc855f20d00966e32ab4965d72821f0a5345",
    "top_layers": "7c6c79d17371db551678e38c82d5f71afd8685b252b464c8e63ea32d47bbaf8f",
}


@pytest.mark.parametrize("name", sorted(FLOPS_PER_STEP))
def test_cell_finds_its_family_and_reads_as_before(name):
    from repro.configs.base import ModelConfig
    cell = Cell.find(load_benchmark(), name)
    assert Path(cell.family.__file__) == BENCH / "families" / "dense_lm.py"
    assert Path(cell.reference.__file__) == BENCH / "references" / "dense_lm.py"
    t = cell.traffic
    flops = cell.family.step_flops(cell.family.dims(cell.config), t["batch"],
                                   t["seq"], t["trainable"])
    assert flops == FLOPS_PER_STEP[name]
    assert cell.family.program_config(cell.config) == \
        ModelConfig(**PROGRAM_CONFIGS[name])


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("trainable", ["all", {"top_layers": 1}],
                         ids=["all", "top_layers"])
def test_initial_state_from_a_seed_is_unchanged(trainable):
    cell = tiny_cell(trainable)
    system = M.System(cell.family, cell.config, cell.traffic)
    state = system.init_state(M.seed_key(2**31 + 5))
    key = "all" if trainable == "all" else "top_layers"
    assert _digest(state) == STATE_DIGESTS[key]


@pytest.mark.parametrize("reference", [None, "no_such_lm",
                                       "../references/dense_lm"])
def test_unknown_reference_is_refused_before_anything_loads(
        reference, tmp_path, monkeypatch):
    config = {k: v for k, v in TINY_CONFIG.items() if k != "reference"}
    if reference is not None:
        config["reference"] = reference
    (tmp_path / "config.json").write_text(json.dumps(config))
    bench = load_benchmark()
    for c in bench["configs"]:
        c["file"] = "config.json"
    monkeypatch.setattr(spec, "ROOT", tmp_path)

    def no_load(kind, name):
        raise AssertionError(f"loaded {kind}/{name} before the refusal")
    monkeypatch.setattr(spec, "load_module", no_load)
    with pytest.raises(SystemExit) as e:
        Cell.find(bench, "florbench-100m.dense_record")
    assert "known: ['dense_lm']" in str(e.value)


@pytest.fixture
def keyed_family(tmp_path, monkeypatch):
    """A copy of the benchmark's families and references beside a dense
    family ``keyed_lm`` that declares it reads the traffic key
    ``probe_every``."""
    bench = tmp_path / "bench"
    for kind in ("families", "references"):
        shutil.copytree(BENCH / kind, bench / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(BENCH / kind / "dense_lm.py", bench / kind / "keyed_lm.py")
    with open(bench / "families" / "keyed_lm.py", "a") as f:
        f.write('\nTRAFFIC_KEYS = {"probe_every"}\n')
    monkeypatch.setattr(spec, "BENCH", bench)


@pytest.mark.parametrize("reference,key,accepted", [
    ("keyed_lm", "probe_every", True),
    ("dense_lm", "probe_every", False),
    ("keyed_lm", "probe_often", False),
])
def test_traffic_key_passes_only_where_the_family_reads_it(
        keyed_family, reference, key, accepted):
    config = dict(TINY_CONFIG, reference=reference)
    traffic = dict(tiny_cell().traffic, **{key: 4})
    if accepted:
        cell = Cell("tiny.record", config, traffic, {}, 1, [], [])
        assert cell.traffic[key] == 4
    else:
        with pytest.raises(SystemExit, match=key):
            Cell("tiny.record", config, traffic, {}, 1, [], [])


def test_family_added_as_new_files_runs_end_to_end(tmp_path):
    """In a copy of the benchmark, a second family and reference (the dense
    ones under another name) are added as new files; a tiny cell that names
    them runs from set-up to a correct comparison."""
    checkout = tmp_path / "checkout"
    shutil.copytree(BENCH, checkout / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", checkout)
    before = {p: p.read_bytes() for p in checkout.rglob("*") if p.is_file()}
    # the twins count their calls, so the run shows it went through them
    for kind, fn in (("families", "program_config"),
                     ("references", "first_steps")):
        twin = checkout / "bench" / kind / "twin_lm.py"
        shutil.copy(BENCH / kind / "dense_lm.py", twin)
        with open(twin, "a") as f:
            f.write(f"\n\nCALLS = []\n_{fn} = {fn}\n\n\n"
                    f"def {fn}(*args, **kwargs):\n"
                    f"    CALLS.append(1)\n"
                    f"    return _{fn}(*args, **kwargs)\n")
    script = textwrap.dedent(f"""
        import json, sys, time
        sys.path[:0] = [{str(checkout / "bench" / "tests")!r},
                        {str(checkout / "bench")!r}, {str(ROOT / "src")!r}]
        from tiny import TINY_CONFIG, tiny_cell
        from harness.runner import run_cell
        cell = tiny_cell(config=dict(TINY_CONFIG, reference="twin_lm"))
        res, _ = run_cell(cell, 2**31 + 83, 0.3, False,
                          t_start=time.monotonic(), rehearsal=True,
                          run_dir={str(tmp_path / "run")!r})
        print(json.dumps({{"family": cell.family.__file__,
                           "reference": cell.reference.__file__,
                           "calls": [len(cell.family.CALLS),
                                     len(cell.reference.CALLS)],
                           "correct": res["correct"]}}))
    """)
    p = subprocess.run([sys.executable, "-c", script],
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONDONTWRITEBYTECODE="1"),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert out["family"] == str(checkout / "bench" / "families" / "twin_lm.py")
    assert out["reference"] == str(checkout / "bench" / "references"
                                   / "twin_lm.py")
    assert out["calls"] == [1, 1] and out["correct"] is True
    assert all(p.read_bytes() == b for p, b in before.items())
