"""Device-mesh construction.

A FUNCTION, so importing the module never touches jax device state: the
caller sets ``--xla_force_host_platform_device_count`` before its first
jax import when it wants several CPU devices.
"""
from __future__ import annotations

from typing import Tuple

import jax


def make_test_mesh(shape: Tuple[int, ...] = (2, 2),
                   axes: Tuple[str, ...] = ("data", "model")
                   ) -> jax.sharding.Mesh:
    """Small mesh for CPU tests (requires >= prod(shape) host devices)."""
    import numpy as np
    n = int(np.prod(shape))
    dev_array = np.asarray(jax.devices()[:n]).reshape(shape)
    return jax.sharding.Mesh(
        dev_array, axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
