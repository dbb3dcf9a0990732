"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single-pod: 16 x 16 = 256 chips ("data", "model").
Multi-pod: 2 x 16 x 16 = 512 chips ("pod", "data", "model"); the "pod" axis
is folded into the data-parallel group by the sharding rules (gradient
all-reduce crosses pods over DCI; everything else stays intra-pod).
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests/smokes)."""
    return _make_mesh((data, model), ("data", "model"))
