"""The comparison that decides ``correct``.

Two layers are compared:

* the train step: the run's first ``CHECK_STEPS`` steps, through the
  window's own step and feed, against the plain float32 reference from the
  same weights and tokens. Compared are each step's loss, the norm of the first gradient as the optimizer got it
  (Adam's first-step mean over ``1 - b1``), and the norm of the trainable
  parameters' change over the steps. Norms are compared by the worst leaf:
  the gap between the program's norm and the reference's, over the larger
  of the reference's norm of that leaf and of the median leaf. Leaves whose
  reference gradient is under a thousandth of the median leaf's move by
  round-off alone and are left out of the change.
* the checkpoint pipeline and the store: a checkpoint taken in the window,
  restored from the store, against the state that was submitted, bit for
  bit.

A run is also not correct when a program compiled inside its window or its
warm-up ended without a steady pipeline (``WINDOW_LIMITS``).
"""
from __future__ import annotations

import numpy as np

from harness.model import B1

ROUNDOFF_GRAD = 1e-3       # of the median leaf's reference gradient norm
WINDOW_LIMITS = {"window_compiles": 0, "warmup_unsteady": 0}


def _norms(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(np.linalg.norm(
        np.asarray(v, np.float64).ravel())) for p, v in flat}


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """max over leaves of |prog - ref| / max(ref_leaf, median ref leaf)."""
    median = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
            for k in ref if keep is None or k in keep]
    return max(gaps) if gaps else 0.0


def program_readings(captured) -> dict:
    """The program's side of the step comparison, in the reference's form:
    each step's loss, the first gradient's per-leaf norms (Adam's first-step
    mean over ``1 - b1``) and the per-leaf norms of the parameters' change."""
    import jax
    tmap = jax.tree_util.tree_map
    return {"losses": list(captured.losses),
            "grad_norms": _norms(tmap(
                lambda m: np.asarray(m, np.float64) / (1 - B1), captured.mu1)),
            "delta_norms": _norms(tmap(
                lambda a, b: np.asarray(a, np.float64) - b,
                captured.p3, captured.p0))}


def step_numbers(got: dict, ref: dict) -> dict:
    """The three step numbers of ``got`` (readings of the program, or of a
    control in its place) against the reference's readings."""
    gmed = float(np.median(list(ref["grad_norms"].values())))
    moving = {k for k, v in ref["grad_norms"].items()
              if v >= ROUNDOFF_GRAD * gmed}
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(got["losses"], ref["losses"]))
    return {"loss_gap": loss_gap,
            "grad_gap": worst_leaf_gap(got["grad_norms"], ref["grad_norms"]),
            "update_gap": worst_leaf_gap(got["delta_norms"],
                                         ref["delta_norms"], moving)}


def store_mismatch(submitted: dict, restored: dict) -> int:
    """Leaves of the submitted state whose restored bytes differ (a leaf
    missing from the checkpoint counts as differing)."""
    bad = 0
    for path, want in submitted.items():
        got = restored.get(path)
        if got is None or got.dtype != want.dtype or got.shape != want.shape \
                or got.tobytes() != want.tobytes():
            bad += 1
    return bad + len(set(restored) - set(submitted))


def flat_paths(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in flat}


def judge(numbers: dict, limits: dict):
    """(correct, {name: {value, limit}}, one line per number) for the
    numbers compared, each against its limit."""
    check = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    lines = [f"check {k} {c['value']!r} limit {c['limit']!r} "
             f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}"
             for k, c in check.items()]
    return all(c["value"] <= c["limit"] for c in check.values()), check, lines
