"""Production mesh construction (a function — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.

    The model pins layouts with ``with_sharding_constraint``, which the
    ``Explicit`` axes that ``jax.make_mesh`` makes by default refuse.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1x1 mesh with production axis names (smoke tests on one device)."""
    return make_mesh((1, 1), ("data", "model"))
