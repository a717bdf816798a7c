"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; tests and benches must keep seeing 1 device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Rules.constrain uses with_sharding_constraint, which only accepts
    # Auto axes (make_mesh defaults to Explicit)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Trivial 1x1 mesh on the real local device (smoke tests, examples)."""
    dev = jax.devices()[0]
    import numpy as np

    return jax.sharding.Mesh(np.array([[dev]]), axis_names=("data", "model"))
