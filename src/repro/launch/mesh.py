"""Production mesh construction.

A FUNCTION, not a module constant: importing this module never touches jax
device state.  Single pod: 16x16 = 256 chips ("data","model").  Multi-pod:
2x16x16 = 512 chips ("pod","data","model") — the "pod" axis is the
data-parallel axis that crosses the inter-pod network.
"""
from __future__ import annotations

import jax


def axis_types_kw(n_axes: int) -> dict:
    """axis_types=(Auto, ...): every mesh axis under GSPMD auto-sharding."""
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **axis_types_kw(len(axes)))


def make_host_mesh() -> jax.sharding.Mesh:
    """1-device mesh for smoke tests / examples on CPU."""
    return jax.make_mesh((1, 1), ("data", "model"), **axis_types_kw(2))
