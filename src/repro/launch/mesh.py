"""Production mesh construction (a function, never module-level state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = one v5e pod (256 chips); 2x16x16 = two pods (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_mesh_for(n_devices: int, model_par: int = 1):
    """Small helper for tests/examples on however many devices exist."""
    assert n_devices % model_par == 0
    if model_par > 1:
        return jax.make_mesh((n_devices // model_par, model_par),
                             ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
    return jax.make_mesh((n_devices,), ("data",),
                         axis_types=(AxisType.Auto,))
