"""Matmul, norms and the float8 rounding shared by the references."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)
E5M2_MAX = float(jnp.finfo(jnp.float8_e5m2).max)


def _round(x, dtype, fmax):
    """``x`` on the float8 grid of ``dtype`` under one amax scale."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, fmax / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    """e4m3 forward, e5m2 for the cotangent: float8 training's rounding."""
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, ct):
    return (_round(ct, jnp.float8_e5m2, E5M2_MAX),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def einsum(spec: str, a, b, *, fp8_operands: bool = False):
    """float32 einsum at HIGHEST; float8-rounded operands for the control."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8_operands:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps: float):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)
