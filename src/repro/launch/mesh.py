"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first init.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips per pod (TPU v5e pod slice); 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh() -> Mesh:
    """Whatever this host actually has (CPU: 1 device) — for examples."""
    n = jax.device_count()
    return jax.make_mesh((n, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
