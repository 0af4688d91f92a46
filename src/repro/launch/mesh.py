"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches JAX device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any JAX
import, and smoke tests must keep seeing 1 device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 = 256-chip pod (``data x model``) or 2x16x16 = 512-chip
    two-pod mesh (``pod x data x model``; ``pod`` is the DCN axis)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Mesh over whatever devices exist (CPU tests / single host)."""
    n = len(jax.devices())
    assert n % model == 0, (n, model)
    return jax.make_mesh(
        (n // model, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )
