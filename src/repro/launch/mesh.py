"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips, axes
(data, model).  Multi-pod: 2 pods x 256 = 512 chips, axes
(pod, data, model); the ``pod`` axis carries pure data parallelism with
gradient all-reduce across the (slower) inter-pod links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=(1, 1), axes=("data", "model")) -> jax.sharding.Mesh:
    """Tiny mesh over however many (CPU) devices exist — for smoke tests."""
    n = len(jax.devices())
    d = min(n, shape[0] * shape[1])
    return jax.make_mesh((d, 1), axes, axis_types=(AxisType.Auto,) * len(axes))
