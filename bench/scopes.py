"""Device self time by program scope, per traced training step or
engine tick, and the program's own host spans per traced step.

The program tags its device work with the XLA frontend attribute
``repro_scope="<scope>"`` (``repro.obs.trace.scope``).  The compiler
keeps it on the compiled ops, and a TPU trace's op event is named by the
op's HLO text, which prints it: ``%fusion.3 = bf16[..] fusion(..),
..., frontend_attributes={repro_scope="ssd"}``.
An op that carries no tag (one the compiler made, such as a layout copy)
counts as ``unscoped``.  Self times (``Trace.ops``) never count a nested
op twice, so the scopes and ``unscoped`` add up to the device time of
the window.

A traced training step is one ``train_step dispatch`` host span of the
window, a traced engine tick one ``engine tick`` span.  Each reader
returns None where it finds nothing to read: no op of the window carries
a tag (a program without scopes), or no step or tick span.
"""
from __future__ import annotations

import re

UNSCOPED = "unscoped"
STEP_SPAN = "train_step dispatch"
TICK_SPAN = "engine tick"
DATA_WAIT = "data wait"
_TAG = re.compile(r'repro_scope="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The scope an op event's name carries, else ``unscoped``."""
    m = _TAG.search(op_name)
    return m.group(1) if m else UNSCOPED


def self_ns_by_scope(ops) -> dict:
    """{scope: device self time (ns)} over ``Trace.ops``."""
    out: dict = {}
    for name, _, _, own in ops:
        key = scope_of(name)
        out[key] = out.get(key, 0) + own
    return out


def _steps(trace, span: str = STEP_SPAN) -> int:
    return sum(1 for name, *_ in trace.host if name == span)


def scope_ms(ctx, scope: str, span: str = STEP_SPAN) -> float | None:
    """Device self time a traced ``span`` (a training step or an engine
    tick) spends under ``scope`` (ms)."""
    trace = ctx.get("trace")
    if trace is None or not _steps(trace, span):
        return None
    by_scope = self_ns_by_scope(trace.ops)
    if set(by_scope) <= {UNSCOPED}:
        return None
    return by_scope.get(scope, 0) * 1e-6 / _steps(trace, span)


def scope_reader(scope: str, span: str = STEP_SPAN):
    """``read(ctx)`` for one scope's metric file."""
    return lambda ctx: scope_ms(ctx, scope, span)


def data_wait_ms(ctx) -> float | None:
    """Host time a traced step spends in the program's ``data wait``
    spans (ms)."""
    trace = ctx.get("trace")
    if trace is None or not _steps(trace):
        return None
    waits = [e - s for name, s, e, _ in trace.host if name == DATA_WAIT]
    if not waits:
        return None
    return sum(waits) * 1e-6 / _steps(trace)
