"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init, and smoke tests must keep seeing 1 device.

Single pod: 16 x 16 = 256 chips (axes data, model).
Multi-pod:  2 x 16 x 16 = 512 chips (axes pod, data, model) — the 'pod'
axis carries pure data parallelism (optionally pipeline stages) whose
collectives cross the inter-pod links.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, *, pod: int = 0) -> Mesh:
    """Small mesh over however many (fake) devices exist — tests use 8."""
    n = len(jax.devices())
    if pod:
        assert n % (pod * model) == 0
        shape = (pod, n // (pod * model), model)
        axes = ("pod", "data", "model")
    else:
        assert n % model == 0
        shape = (n // model, model)
        axes = ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
