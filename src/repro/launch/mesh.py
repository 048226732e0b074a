"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state (jax locks the device count on first backend init — the dry-run
must set XLA_FLAGS before any jax call).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2x16x16 = 512 chips (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """A mesh whose axes are all ``Auto``: the compiler propagates
    shardings through gathers and reshapes. (``jax.make_mesh`` defaults
    to ``Explicit`` axes, under which every such op must state its output
    sharding.) ``devices`` defaults to ``jax.devices()``; on CPU, force
    enough of them with --xla_force_host_platform_device_count."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )
