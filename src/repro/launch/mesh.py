"""Production mesh construction (TPU v5e pods: 16×16 = 256 chips per pod).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state.  Every axis is ``Auto``: the sharding rules place
arrays with ``with_sharding_constraint``, which ``jax.make_mesh``'s default
``Explicit`` axes refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, examples)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
