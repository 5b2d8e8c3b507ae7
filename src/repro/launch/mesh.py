"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — required by the dry-run, which must
set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "dp_axes", "DATA", "MODEL",
           "POD"]

POD, DATA, MODEL = "pod", "data", "model"


def _make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto (not the default)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) ("data", "model") = 256 chips.
    Multi-pod: (2, 16, 16) ("pod", "data", "model") = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (POD, DATA, MODEL) if multi_pod else (DATA, MODEL)
    return _make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests / elastic re-meshing)."""
    return _make_mesh(shape, axes)


def dp_axes(mesh) -> tuple:
    """Batch-sharding axes: ('pod', 'data') when a pod axis exists."""
    names = mesh.axis_names
    return tuple(a for a in (POD, DATA) if a in names)
