"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; smoke tests and benchmarks see the default single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_test_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for subprocess unit tests (8 host devices).  Axes are
    ``Auto``: the sharding rules here are GSPMD specs, not sharding-in-types
    (``jax.make_mesh``'s default ``Explicit`` axes reject them)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
