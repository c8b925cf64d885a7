"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches JAX device state — the dry-run must set
``XLA_FLAGS`` before the first device query, and smoke tests must keep
seeing one CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, names, devices=None):
    """``jax.make_mesh`` with every axis Auto (sharding propagated by XLA)."""
    return jax.make_mesh(shape, names, axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16×16 per pod; 2 pods when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small explicit mesh for tests (requires forced host device count)."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def single_device_mesh():
    return make_host_mesh(1, 1)
