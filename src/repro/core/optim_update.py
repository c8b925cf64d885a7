"""Loss-scaling-aware optimizer step — Section 3.5 of the MPX paper.

``optimizer_update(model, optimizer, optimizer_state, grads, grads_finite)``
replaces the usual ``optimizer.update(...)`` + ``apply_updates(...)`` pair
and applies the update *only when the gradients are finite* — the skipped
step is how dynamic loss scaling recovers from an overflow without poisoning
the parameters or the optimizer moments.

Works with any optimizer following the optax ``init/update`` protocol
(``repro.optim`` provides AdamW/SGD/Adafactor implementations).  The select
is a pair of ``jnp.where``-on-pytrees, which XLA fuses into the update — a
skipped step costs the same FLOPs but commits no state change.

Device scope (:func:`repro.obs.trace.scope`): ``optimizer``, the select
included.  XLA fuses the select into the update, and a fusion carries the
scope of its root: under ``mpx`` the select would charge the whole fused
update to mixed precision.
"""
from __future__ import annotations

from typing import Any

import jax

from repro.core.filtering import (combine, is_inexact_array, partition,
                                  select_tree)
from repro.obs.trace import scope

PyTree = Any


def apply_updates(model: PyTree, updates: PyTree) -> PyTree:
    """``model + updates`` over inexact leaves; ``None`` updates are skipped.

    Update leaves are cast to the parameter dtype before the add so a
    half-precision update cannot silently downcast an fp32 master param.
    """

    def _add(p, u):
        if u is None or p is None:
            return p
        return p + u.astype(p.dtype) if is_inexact_array(p) else p

    return jax.tree.map(_add, model, updates,
                        is_leaf=lambda x: x is None)


def optimizer_update(model: PyTree, optimizer, optimizer_state: PyTree,
                     grads: PyTree, grads_finite: jax.Array,
                     ) -> tuple[PyTree, PyTree]:
    """Conditionally-applied optimizer step (paper Example 2b).

    Returns ``(new_model, new_optimizer_state)``; both are unchanged when
    ``grads_finite`` is False.
    """
    params, static = partition(model, is_inexact_array)
    with scope("optimizer"):
        updates, new_opt_state = optimizer.update(grads, optimizer_state,
                                                  params=params)
        new_params = apply_updates(params, updates)
        new_params = select_tree(grads_finite, new_params, params)
        new_opt_state = select_tree(grads_finite, new_opt_state,
                                    optimizer_state)
    return combine(new_params, static), new_opt_state
