"""Parameter metadata: shapes + logical sharding axes + initializers.

Models in this framework describe their parameters as trees of
:class:`ParamSpec` (shape, logical axis names, init law).  From one spec
tree we derive, without ever tracing the model:

- ``abstract(spec_tree)``      -> ShapeDtypeStruct tree (dry-run stand-ins)
- ``initialize(key, spec_tree)``-> materialized fp32 parameters
- ``pspecs(spec_tree, mesh, rules)`` -> NamedSharding tree (via
  :mod:`repro.sharding.rules`)

This is the MaxText/Flax "logical axis" pattern reduced to its essentials,
and it is what lets the 512-device dry-run lower full-size models on a CPU
without allocating a byte of parameter memory.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    """Declarative description of one parameter tensor."""
    shape: tuple[int, ...]
    logical: tuple[Optional[str], ...]   # logical axis name per dim
    init: str = "normal"                 # normal|zeros|ones|embed|trunc_fan_in
    scale: float = 1.0                   # multiplier on the init law
    dtype: Any = jnp.float32
    #: input features of the fan-in laws; None: the first dim (rank >= 2)
    fan_in: Optional[int] = None

    def __post_init__(self):
        if len(self.shape) != len(self.logical):
            raise ValueError(
                f"shape {self.shape} and logical {self.logical} rank mismatch")


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def abstract(spec_tree: PyTree) -> PyTree:
    """ShapeDtypeStruct stand-ins (no allocation) for a spec tree."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), spec_tree,
        is_leaf=is_spec)


def _fan_in(spec: ParamSpec) -> int:
    if spec.fan_in is not None:
        return spec.fan_in
    return spec.shape[0] if len(spec.shape) >= 2 else max(spec.shape[-1], 1)


def _init_one(key, spec: ParamSpec):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "normal":
        # LeCun-style fan-in scaling on the input features
        std = spec.scale / math.sqrt(_fan_in(spec))
        return (jax.random.normal(key, spec.shape, spec.dtype) * std)
    if spec.init == "embed":
        std = spec.scale
        return jax.random.normal(key, spec.shape, spec.dtype) * std
    if spec.init == "trunc_fan_in":
        std = spec.scale / math.sqrt(_fan_in(spec))
        return (jax.random.truncated_normal(key, -2.0, 2.0, spec.shape,
                                            spec.dtype) * std)
    raise ValueError(f"unknown init {spec.init!r}")


def initialize(key: jax.Array, spec_tree: PyTree) -> PyTree:
    """Materialize fp32 parameters; one fold of the key per leaf."""
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves)) if leaves else []
    params = [_init_one(k, s) for k, s in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, params)


def logical_axes(spec_tree: PyTree) -> PyTree:
    """Tree of logical-axis tuples matching the spec tree's structure."""
    return jax.tree.map(lambda s: s.logical, spec_tree, is_leaf=is_spec)


def count_params(spec_tree: PyTree) -> int:
    return sum(math.prod(s.shape)
               for s in jax.tree.leaves(spec_tree, is_leaf=is_spec))


def stack_specs(spec_tree: PyTree, n: int, axis_name: Optional[str] = "layers",
                ) -> PyTree:
    """Prepend a stacking dim of size ``n`` to every spec (scan-over-layers);
    each layer keeps its own fan-in."""

    def _stack(s: ParamSpec) -> ParamSpec:
        return ParamSpec((n,) + s.shape, (axis_name,) + s.logical,
                         init=s.init, scale=s.scale, dtype=s.dtype,
                         fan_in=_fan_in(s))

    return jax.tree.map(_stack, spec_tree, is_leaf=is_spec)
