"""Production mesh construction (assignment MULTI-POD DRY-RUN spec).

A function, not a module-level constant — importing this module never
touches jax device state.
"""

from __future__ import annotations

import jax

__all__ = ["make_auto_mesh", "make_production_mesh"]


def make_auto_mesh(shape, names) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis of ``Auto`` type (sharding
    propagated by the compiler, as the GSPMD rules expect)."""
    return jax.make_mesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 (single v5e-class pod, 256 chips) or 2x16x16 (2 pods, 512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)
