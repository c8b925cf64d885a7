"""Driver for ``serve_open`` traffic: arrivals at the mix's fixed mean
rate (``traffic.arrivals``), judged by the tails of time to first token
and between tokens.

Requests are due on a schedule drawn from the seed, whatever the engine
is doing; each is submitted at the first tick boundary at or after its
due time, and its time to first token runs from when it was due, so a
stall that delays sending is charged.  Arrivals start ``preroll_s``
before the window, so that the window sees the queue in its steady
state.  After the window closes, arrivals go on until every request due
in the window has its first token (at most ``grace_s``); one that has
none by then counts as slower than every other.

- ``ttft_p90_ms``: 90th percentile over every request due in the window;
- ``itl_p95_ms``: 95th percentile of every gap between consecutive
  tokens of one request whose later token came in the window.
"""
from __future__ import annotations

import gc
import math
import time

from bench import harness, serving, traffic


def run(run: harness.Run) -> harness.Outcome:
    from repro.obs.trace import Tracer
    mix, log = run.cell.traffic, run.log
    tracer = Tracer() if run.trace else None
    cfg, params, engine = serving.make_engine(run, tracer)
    serving.warm(engine, 40, log)
    book = serving.Book(engine)
    span = mix["preroll_s"] + run.seconds + mix["grace_s"]
    n = int(mix["rate_per_s"] * span * 1.5) + 64
    _, reqs = traffic.requests(mix, n, run.seed, cfg.vocab_size)
    due = traffic.arrivals(mix["rate_per_s"], n, traffic.rng_for(run.seed, 2))
    built = harness.compiles()
    t0 = time.perf_counter()
    w0 = t0 + mix["preroll_s"]
    w1 = w0 + run.seconds
    setup_s = w0 - run.t_start
    # the traced segment closes the window: stopping a profiler trace
    # holds the engine for about as long as the segment, so it falls in
    # the grace, and the queue wait is read over the requests due before
    ticker = serving.Traced(run, book, tracer,
                            at=max(w0, w1 - mix["trace_s"]),
                            seconds=mix["trace_s"])

    def done(now: float) -> bool:
        """Past the window, once every request due in it has its first
        token and the trace has stopped, or the grace is spent."""
        if now < w1:
            return False
        if now >= w1 + mix["grace_s"]:
            return True
        waiting = [r for r in book.reqs.values()
                   if w0 <= r.due < w1 and not r.token_times]
        return not waiting and not ticker.tracing

    log(f"arrivals start; the window opens after {mix['preroll_s']}s")
    serving.open_loop(book, reqs, due, t0, done, ticker.tick)
    log(f"window closed; setup_s {setup_s:.3f}; {book.ticks} ticks in all, "
        f"{time.perf_counter() - w1:.3f}s of grace; "
        f"{harness.compiles() - built} programs built since arrivals began")
    window = [r for r in book.reqs.values() if w0 <= r.due < w1]
    ttft = [(r.token_times[0] - r.due) * 1e3 if r.token_times else math.inf
            for r in window]
    itl = [(b - a) * 1e3 for r in book.reqs.values()
           for a, b in zip(r.token_times, r.token_times[1:]) if w0 <= b < w1]
    wait = [(r.admitted - r.due) * 1e3 if not math.isnan(r.admitted)
            else math.inf for r in window if r.due < ticker.at]
    failed = sum(r.status not in (None, "ok") for r in window)
    log(f"{len(window)} requests due in the window, {len(itl)} token gaps; "
        f"{sum(r.status == 'ok' for r in window)} finished")
    memory = harness.memory_peak(run.devices)
    book.engine = engine = None
    gc.collect()
    picked = serving.sample(book, traffic.rng_for(run.seed, 3), w0,
                            mix["check"]["min_tokens"],
                            mix["check"]["max_requests"])
    gap, control, _ = serving.logit_gap(run, params, book, picked,
                                        fp8=run.control)
    layer = ticker.layer()
    if layer:
        layer["queue_wait_ms"] = wait
    return harness.Outcome(
        setup_s=setup_s,
        e2e={"ttft_p90_ms": serving.pct(ttft, 0.90),
             "itl_p95_ms": serving.pct(itl, 0.95)},
        attempted=len(window), failed=failed, checks={"logit_gap": gap},
        control={"logit_gap": control} if run.control else {},
        memory_peak_bytes=memory, layer=layer)
