"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls this.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_local_mesh"]

_AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data × model).
    Multi-pod: 2×16×16 = 512 chips (pod × data × model) — the pod axis is the
    DCN/cross-pod data-parallel dimension."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(_AUTO,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(_AUTO, _AUTO))
