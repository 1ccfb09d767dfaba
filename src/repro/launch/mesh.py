"""Production mesh construction.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before any jax import
and only then builds the mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """A device mesh whose axes are all ``Auto``: the logical-axis rules
    place activations with ``with_sharding_constraint``, which refers to
    Auto axes only (``jax.make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# TPU v5e-class hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
