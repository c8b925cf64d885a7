"""Device time of a traced training step by scope, with the Pallas
kernels counted where the program calls them, for the hybrid cell's
readers.

The splash-attention kernels (``jax.experimental.pallas.ops.tpu.
splash_attention``) pass their own ``metadata`` to ``pallas_call``, and
the TPU lowering then writes the custom call's frontend attributes anew
(``kernel_metadata``), dropping the ``repro_scope`` tag the program put
there.  Their op events keep the kernel's name (``%splash_mha_fwd_...``),
so an untagged op of that name is counted under ``attention``, where the
program calls it; every other op keeps the scope its tag names, and the
times are ``bench.scopes.scope_ms``'s.
"""
from __future__ import annotations

import dataclasses

from bench import scopes, trace_reduce

SPLASH = "%splash_"
_ATTENTION_TAG = ', frontend_attributes={repro_scope="attention"}'


def scope_of(name: str) -> str:
    """The op's scope, with untagged splash kernels under ``attention``."""
    scope = scopes.scope_of(name)
    if scope == scopes.UNSCOPED and name.startswith(SPLASH):
        return "attention"
    return scope


def _tagged(ctx) -> dict:
    """``ctx`` with each untagged splash kernel's op carrying the
    ``attention`` tag."""
    trace = ctx.get("trace")
    if trace is None:
        return ctx
    ops = [(name + _ATTENTION_TAG
            if scope_of(name) != scopes.scope_of(name) else name, s, e, own)
           for name, s, e, own in trace.ops]
    return {**ctx, "trace": dataclasses.replace(trace, ops=ops)}


def scope_ms(ctx, scope: str) -> float | None:
    """``bench.scopes.scope_ms`` with the splash kernels attributed."""
    return scopes.scope_ms(_tagged(ctx), scope)


def scope_reader(scope: str):
    return lambda ctx: scope_ms(ctx, scope)


def kernel_roofline(ctx, scope: str, calls: list) -> float | None:
    """Share (%) of the least time of the traced steps' calls of the
    Pallas kernels under ``scope`` (``calls``: one step's (operations,
    bytes) of each call, bounded by ``costs.roofline_seconds``) over
    those kernels' device time in the trace.  None where no such kernel
    ran."""
    from bench.costs import hybrid_moe_lm as costs
    trace = ctx.get("trace")
    steps = sum(1 for name, *_ in trace.host
                if name == scopes.STEP_SPAN) if trace is not None else 0
    if not steps:
        return None
    own = sum(ns for name, _, _, ns in trace.ops
              if trace_reduce.is_kernel(name) and scope_of(name) == scope)
    if own <= 0:
        return None
    least = steps * costs.roofline_seconds(calls, ctx["peaks"])
    return 100.0 * least / (own * 1e-9)
