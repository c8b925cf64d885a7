"""Per-layer readers over a traced run's context.

Each file ``layer_metrics/<metric>.py`` binds ``read`` to one of these.
A reader that finds nothing to read returns None, and the metric is left
out of the line; a share of a peak or a roofline is never reported as 0
for want of data.

The context (``Outcome.layer``) holds ``trace`` (``trace_reduce.Trace``)
and ``peaks``; a training run adds ``flops`` (the traced steps' model
operations, by the configuration's ``costs`` module), a serving run
``plans`` (each traced tick's per-slot ``(start, valid)``), ``host_ms``,
``model``, ``costs`` (that module), ``engine`` and, for an open loop,
``queue_wait_ms``.
"""
from __future__ import annotations

from bench.costs import paged_attention
from bench.serving import pct


def idle_share(ctx) -> float | None:
    """Share of the traced window in which no op ran on the device (%)."""
    trace = ctx.get("trace")
    return None if trace is None else 100.0 * trace.idle_share


def train_step_mfu(ctx) -> float | None:
    """Model operations of the traced steps (forward and backward, no
    recomputation) over the traced window, over the chip's bf16 peak (%)."""
    if "flops" not in ctx:
        return None
    return (100.0 * ctx["flops"] / ctx["trace"].window_s
            / ctx["peaks"]["bf16_flops_per_s"])


def serve_step_mfu(ctx) -> float | None:
    """Operations the traced ticks' valid tokens require (the
    configuration's ``costs.tick_flops``), over the traced window, over
    the chip's bf16 peak (%)."""
    plans = ctx.get("plans")
    if not plans:
        return None
    m = ctx["model"]
    flops = sum(ctx["costs"].tick_flops(m, s, v) for s, v in plans)
    return (100.0 * flops / ctx["trace"].window_s
            / ctx["peaks"]["bf16_flops_per_s"])


def paged_attention_roofline(ctx) -> float | None:
    """The kernel calls' least time at the chip's peaks (each call bound
    by compute or by HBM bandwidth) over the kernel's summed device
    time in the trace (%)."""
    plans = ctx.get("plans")
    trace = ctx.get("trace")
    if not plans or trace is None or trace.kernel_seconds() <= 0:
        return None
    m, eng = ctx["model"], ctx["engine"]
    least = sum(paged_attention.roofline_seconds(
        *paged_attention.tick_cost(m, s, v, page_size=eng["page_size"],
                                   chunk=eng["chunk_size"]), ctx["peaks"])
        for s, v in plans)
    return 100.0 * least / trace.kernel_seconds()


def host_ms_per_tick(ctx) -> float | None:
    """Mean over traced ticks of the tick's wall time minus its
    ``host sync`` span: what the host spent outside the wait (ms)."""
    ms = ctx.get("host_ms")
    return sum(ms) / len(ms) if ms else None


def queue_wait_ms_p90(ctx) -> float | None:
    """90th percentile over the window's requests of admission time
    minus due time (ms)."""
    waits = ctx.get("queue_wait_ms")
    return pct(waits, 0.90) if waits else None
