"""Mixed-precision gradient transforms — Section 3.4 of the MPX paper.

``filter_grad`` / ``filter_value_and_grad`` are drop-in replacements for the
Equinox gradient transforms that add mixed precision + dynamic loss scaling.
The transformed function, applied to ``(model, *args, **kwargs)``:

1. casts all inputs (model and batch) to half precision,
2. runs the original forward + loss,
3. multiplies the loss by the current scaling factor,
4. differentiates w.r.t. the *inexact array leaves of the first argument*
   (master fp32 parameters — the half-precision cast is inside the
   differentiated graph, so cotangents flow back through it and arrive
   already converted to fp32),
5. unscales the gradients (divide by scaling, in fp32),
6. reduces an ``all-finite`` bit over the gradients,
7. adjusts the loss-scaling state,
8. returns ``(new_scaling, grads_finite, grads[, aux])`` —
   ``filter_value_and_grad`` inserts the (unscaled, fp32) loss value before
   the gradients.

With ``use_mixed_precision=False`` the same code path degrades gracefully to
plain full-precision differentiation with the identical return signature, so
a pipeline can be A/B'd by flipping one flag (this is what the paper's
fp32-vs-mixed figures do).

Steps 1, 3 and 5-7 run under the ``mpx`` device scope
(:func:`repro.obs.trace.scope`), so a profiler trace shows what mixed
precision costs a step.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.casting import cast_to_half_precision, half_dtype, cast_tree
from repro.core.filtering import combine, is_inexact_array, partition
from repro.core.loss_scaling import DynamicLossScaling, NoOpLossScaling, all_finite
from repro.obs.trace import scope

PyTree = Any


def filter_value_and_grad(func, scaling, *, has_aux: bool = False,
                          use_mixed_precision: bool = True,
                          compute_dtype=None):
    """Mixed-precision ``value_and_grad`` with dynamic loss scaling.

    Args:
      func: ``func(model, *args, **kwargs) -> loss`` or ``(loss, aux)``.
      scaling: a :class:`DynamicLossScaling` (or ``NoOpLossScaling``).
      has_aux: whether ``func`` returns ``(loss, aux)``.
      use_mixed_precision: disable to get a full-precision pipeline with the
        same return signature.
      compute_dtype: override the half dtype for this transform (defaults to
        the global ``mpx.half_dtype()``).

    Returns a function returning
    ``(new_scaling, grads_finite, value, grads)`` (+ ``aux`` appended to
    ``value`` as ``(value, aux)`` when ``has_aux``).
    """
    cdtype = compute_dtype if compute_dtype is not None else None

    @functools.wraps(func)
    def transformed(model, *args, **kwargs):
        diff, static = partition(model, is_inexact_array)

        def scaled_loss_fn(diff_part, *a, **kw):
            m = combine(diff_part, static)
            if use_mixed_precision:
                dt = cdtype if cdtype is not None else half_dtype()
                with scope("mpx"):
                    m = cast_tree(m, dt)
                    a = cast_tree(a, dt)
                    kw = cast_tree(kw, dt)
            out = func(m, *a, **kw)
            loss, aux = (out if has_aux else (out, None))
            with scope("mpx"):
                scaled = scaling.scale(loss)
            return scaled, (loss, aux)

        grad_fn = jax.value_and_grad(scaled_loss_fn, has_aux=True)
        (_, (loss, aux)), grads = grad_fn(diff, *args, **kwargs)

        with scope("mpx"):
            grads = scaling.unscale(grads)       # fp32 grads, original scale
            grads_finite = all_finite(grads)
            new_scaling = scaling.adjust(grads_finite)
        value = loss.astype(jnp.float32)
        if has_aux:
            return new_scaling, grads_finite, (value, aux), grads
        return new_scaling, grads_finite, value, grads

    return transformed


def filter_grad(func, scaling, *, has_aux: bool = False,
                use_mixed_precision: bool = True, compute_dtype=None):
    """Gradient-only variant: returns ``(new_scaling, grads_finite, grads[, aux])``.

    Mirrors the paper's Example 2::

        loss_scaling, grads_finite, grads = mpx.filter_grad(loss, loss_scaling)(
            model, batch)
    """
    vag = filter_value_and_grad(func, scaling, has_aux=has_aux,
                                use_mixed_precision=use_mixed_precision,
                                compute_dtype=compute_dtype)

    @functools.wraps(func)
    def transformed(model, *args, **kwargs):
        out = vag(model, *args, **kwargs)
        new_scaling, grads_finite, value, grads = out
        if has_aux:
            _, aux = value
            return new_scaling, grads_finite, grads, aux
        return new_scaling, grads_finite, grads

    return transformed
