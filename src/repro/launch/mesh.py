"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so that
importing this module touches no jax device state. The dry-run initializes the
512 placeholder host devices *before* importing anything from ``repro``.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod mesh: 16x16 = 256 chips/pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (smoke tests, elastic remesh plans)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
