"""Normalization layers with fp32 statistics (the paper's Example 1 rule).

Sums/means are exactly the operations MPX forces to full precision.  Both
norms here compute their statistics under ``mpx.force_full_precision`` and
cast the result back to the activation dtype, so a bf16/fp16 forward pass
never accumulates a mean or variance in half precision.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp

from repro import mpx
from repro.nn.param import ParamSpec


def rmsnorm_spec(dim: int, logical: str = "embed"):
    return {"scale": ParamSpec((dim,), (logical,), init="ones")}


def layernorm_spec(dim: int, logical: str = "embed"):
    return {"scale": ParamSpec((dim,), (logical,), init="ones"),
            "bias": ParamSpec((dim,), (logical,), init="zeros")}


#: each norm's eps where the configuration gives none
RMS_EPS, LN_EPS = 1e-6, 1e-5


def _rms_stats(x32: jnp.ndarray, eps: float) -> jnp.ndarray:
    return jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)


def rmsnorm(params, x: jnp.ndarray, eps: float = RMS_EPS) -> jnp.ndarray:
    """RMSNorm; statistics in fp32, output in ``x.dtype``."""
    rms = mpx.force_full_precision(functools.partial(_rms_stats, eps=eps),
                                   None)(x)
    y = (x.astype(jnp.float32) / rms) * params["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def _ln_stats(x32: jnp.ndarray):
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mean) ** 2, axis=-1, keepdims=True)
    return mean, var


def layernorm(params, x: jnp.ndarray, eps: float = LN_EPS) -> jnp.ndarray:
    """LayerNorm; statistics in fp32, output in ``x.dtype``."""
    mean, var = mpx.force_full_precision(_ln_stats, None)(x)
    inv = (var + eps) ** -0.5  # fp32
    y = (x.astype(jnp.float32) - mean) * inv
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def norm_spec(kind: str, dim: int):
    if kind == "rmsnorm":
        return rmsnorm_spec(dim)
    if kind == "layernorm":
        return layernorm_spec(dim)
    raise ValueError(kind)


def apply_norm(kind: str, params, x, eps: float | None = None):
    """``kind``'s norm with ``eps``, or with its own default if None."""
    if kind == "rmsnorm":
        return rmsnorm(params, x, RMS_EPS if eps is None else eps)
    return layernorm(params, x, LN_EPS if eps is None else eps)


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 logit soft-capping ``cap·tanh(x/cap)`` in fp32.

    tanh saturates (and its gradient dies) quickly in bf16; running the cap
    in fp32 is the kernel-level analogue of the paper's
    ``force_full_precision``d softmax.
    """
    if cap <= 0.0:
        return x

    def _cap(x32):
        return cap * jnp.tanh(x32 / cap)

    return mpx.force_full_precision(_cap, x.dtype)(x)
