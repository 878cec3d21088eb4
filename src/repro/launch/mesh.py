"""Production mesh builders (TPU v5e pods).

Functions, not module-level constants — importing this module never touches
jax device state. The dry-run sets XLA_FLAGS for 512 host devices BEFORE
importing jax (see dryrun.py); everything else sees the real device count.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_smoke_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 (256-chip pod) or 2x16x16 (two pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # jax.make_mesh defaults to Explicit axes, which with_sharding_constraint
    # refuses; the model code shards by constraints, so ask for Auto
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_smoke_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for multi-device CPU tests (8 forced host devices)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
