"""Production mesh construction (function, not module constant — importing
this module never touches jax device state).

The meshes keep the Auto axis type, which ``jax.make_mesh`` no longer
defaults to: the LM layers rely on automatic sharding propagation.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(tp: int = 1):
    """Tiny mesh over whatever devices exist (CPU smoke tests / examples)."""
    n = len(jax.devices())
    tp = min(tp, n)
    while n % tp:
        tp -= 1
    return _auto_mesh((n // tp, tp), ("data", "model"))
