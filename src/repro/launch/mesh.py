"""Production mesh construction.

Called as a FUNCTION so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def _mesh(shape, axes):
    """jax.make_mesh with every axis Auto-sharded."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh over the single local device (CPU smoke tests)."""
    return _mesh((1, 1), ("data", "model"))
