"""Random weights made by the benchmark, on the device, in one jitted call.

The benchmark makes the weights itself, from ``--seed``, so that the
plain references under ``bench/reference`` take nothing the program has
made.  The layout is the program's parameter tree (its shapes only); the
values follow the published initialisations:

- embeddings: normal, std 0.02;
- projections: normal, std 1/sqrt(input features) (LeCun fan-in);
- norm scales and ``D``: ones; biases: zeros;
- Mamba-2 SSD: ``A_log = log U(1, 16)``, ``dt`` log-uniform in
  [0.001, 0.1] stored as its inverse softplus in ``dt_bias``, the causal
  conv kernels normal with std 1/sqrt(kernel width) (Dao & Gu 2024).

Keys are RBG keys (XLA's RngBitGenerator), which a TPU draws far faster
than threefry; each leaf folds its index into the seed's key.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: leaves whose input features are their first two axes (after the
#: stacked layer axis): attention's output projection (H, D, d)
_TWO_INPUT_AXES = ("wo",)
_ZEROS = ("bias", "bq", "bk", "bv", "bo", "b_up", "b_down", "b_gate")
_ONES = ("scale", "D", "norm_w")


def seed_key(seed: int) -> jax.Array:
    """An RBG key for any non-negative seed (wider than 32 bits too)."""
    key = jax.random.key(seed & 0xFFFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 32)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _stacked(path) -> bool:
    return any(getattr(p, "key", None) == "scan" for p in path)


def _init(key, name: str, shape, stacked: bool, dtype):
    """One leaf of the published initialisation."""
    core = shape[1:] if stacked else shape
    if name in _ZEROS:
        return jnp.zeros(shape, dtype)
    if name in _ONES:
        return jnp.ones(shape, dtype)
    if name == "tok":
        return (jax.random.normal(key, shape, jnp.float32) * 0.02
                ).astype(dtype)
    if name == "A_log":
        a = jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
        return jnp.log(a).astype(dtype)
    if name == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name.startswith("conv_"):
        std = 1.0 / math.sqrt(core[0])
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    fan_in = (core[0] * core[1] if name in _TWO_INPUT_AXES else core[0])
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make(shapes, seed: int, dtype):
    """Parameters shaped like ``shapes`` (a ShapeDtypeStruct tree), in
    ``dtype``, made on the default device by one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = [_init(jax.random.fold_in(key, i), _leaf_name(path),
                        sd.shape, _stacked(path), dtype)
                  for i, (path, sd) in enumerate(flat)]
        return jax.tree.unflatten(treedef, leaves)

    return jax.jit(build)(seed_key(seed))
