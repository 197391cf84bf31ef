"""Mesh construction.

Every mesh in the repo is built here, through :func:`make_mesh`.  Its
axes are ``AxisType.Auto``: the model and the dist runtime place
values with ``with_sharding_constraint`` and let GSPMD propagate, and
that constraint refers only to Auto axes.  (``jax.make_mesh`` makes
Explicit axes by default in jax 0.9.)

These are FUNCTIONS (not module-level constants) so importing this
module never touches jax device state.  Production layouts: single pod
16 x 16 = 256 chips ("data", "model"); multi-pod 2 x 16 x 16 = 512
chips ("pod", "data", "model").
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int],
              axes: Sequence[str]) -> jax.sharding.Mesh:
    """A mesh over ``jax.devices()`` with Auto axes (see the module
    docstring)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
