"""Production mesh builders.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """jax.make_mesh with every axis in automatic sharding mode."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod mesh, or 2x16x16 across two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Same axis names over however many devices exist (CPU tests)."""
    n = jax.device_count()
    if multi_pod:
        return make_auto_mesh((1, n, 1), ("pod", "data", "model"))
    return make_auto_mesh((n, 1), ("data", "model"))

