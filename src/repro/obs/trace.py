"""Tracing: a Chrome-trace span recorder, device scopes and program spans.

Three instruments:

- :class:`Tracer` records host spans on its own ``perf_counter`` clock
  and exports Chrome trace-event JSON (Perfetto-loadable).  Dependency-
  free, host-side, bounded; it never touches a device array, so enabling
  it adds zero device syncs to the hot path (pinned by a test).
- :func:`scope` tags device work at trace time.  Every op traced inside
  ``scope(name)`` carries ``jax.named_scope(name)`` (the ``tf_op`` that
  xprof and TensorBoard group by) and the XLA frontend attribute
  ``repro_scope=name``, which the compiler keeps on the compiled ops and
  a TPU profiler trace prints in each op event's name.  The innermost
  scope wins.  The tag is compile-time only; it changes no op.
- :func:`program_span` opens a ``jax.profiler.TraceAnnotation`` host
  span on the profiler's own clock (the trainer and the engine wrap each
  step in a ``StepTraceAnnotation``), so a device trace can put each idle
  gap down to a program phase.  With no profiler running it costs about
  a microsecond.  :class:`PhaseSpans` opens back-to-back spans for a
  step whose phases follow one another.

Device scopes (:data:`SCOPES`), placed in ``models/transformer``,
``core/grad``, ``core/optim_update`` and ``train/steps``: ``embed``;
``layers`` (the ``lax.scan`` over blocks and what it stacks and copies,
plus the norms and residual adds outside the mixer and MLP calls); one
per mixer or MLP kind (``ssd``, ``attention``, ``rglru``, ``mlp``,
``moe``); ``moe_route`` inside ``moe`` (an expert-only block's router,
top-k, sort, dispatch and combine, apart from the expert matmuls);
``unembed_loss`` (final norm, logits, cross-entropy); ``mpx``
(the cast to the compute dtype and its transposed upcast, loss scaling,
unscale, the finite check, the scale adjust); ``optimizer`` (clip or
global norm, the optimizer update with the skip-step select XLA fuses
into it, the ZeRO-1 grad sharder); ``sampling`` (the serving sampler).
A fused op carries the scope of its root instruction.

Program spans (:data:`SPANS`): ``data wait`` (``Prefetcher.next_batch``
blocked on its queue), ``host sync`` and ``checkpoint`` inside each
``train`` step of ``Trainer.fit``; ``admit``, ``plan``, ``device step``,
``host sync`` and ``commit`` inside each ``serve`` step of
``ServeEngine.step``.

Tracer model:

- a :class:`Tracer` holds a ring buffer (``collections.deque(maxlen=...)``)
  of trace events — a runaway serve session overwrites its oldest events
  instead of growing without bound;
- the clock is injectable (``Tracer(clock=...)``) so tests can drive
  deterministic timelines; timestamps are microseconds relative to tracer
  construction (Chrome trace ``ts``);
- **spans** are "X" (complete) events with ``ts`` + ``dur`` — emitted on
  exit, so they nest exactly (a child's end is measured before its
  parent's);  **instants** are "i" events; ``thread_name`` metadata ("M")
  labels the per-``tid`` tracks.

Track convention used by :class:`repro.serve.engine.ServeEngine`:

- ``tid 0`` ("engine") carries the per-tick phases — ``tick`` wrapping
  ``admit`` / ``plan`` / ``device step`` / ``host sync`` / ``commit``;
- ``tid 1 + slot`` ("slot N") carries that slot's request lifecycle:
  ``submit``/``admit`` instants, one ``prefill`` span per chunk, one
  ``decode`` span per (speculative) window with
  ``{rid, tokens, drafts, accepted}`` args, ``truncate`` instants when a
  window's tail is rejected, and a ``retire`` instant.

Load the exported file in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing``: a serve session renders as one timeline per slot
over the engine-phase track.

:func:`validate_chrome_trace` checks the schema the CI artifact relies on
(every event has ``ph``/``ts``/``pid``/``tid``; spans nest within a
track) without needing a browser; :func:`profiler_trace` is the optional
``jax.profiler`` hook — a context manager that brackets a run with
``start_trace``/``stop_trace`` when given a directory and is a no-op
otherwise.

This module imports JAX only inside the functions that need it, so
``import repro.obs`` stays cheap for stdlib-only consumers.
"""
from __future__ import annotations

import json
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, Iterable, List, Optional, Union


class Tracer:
    """Bounded ring-buffer trace recorder with an injectable clock."""

    def __init__(self, clock=time.perf_counter, max_events: int = 65536,
                 pid: int = 0, process_name: str = "repro"):
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1: {max_events}")
        self._clock = clock
        self._t0 = clock()
        self.pid = pid
        self.events: deque = deque(maxlen=max_events)
        # metadata events live outside the ring buffer: a long session
        # must not evict its track names
        self._meta: List[dict] = [{
            "ph": "M", "ts": 0.0, "pid": pid, "tid": 0,
            "name": "process_name", "args": {"name": process_name}}]
        self._named_tids: set = set()

    # -- recording ----------------------------------------------------------

    def now_us(self) -> float:
        """Microseconds since tracer construction (trace ``ts`` units)."""
        return (self._clock() - self._t0) * 1e6

    def thread_name(self, tid: int, name: str) -> None:
        """Label a track (idempotent per tid)."""
        if tid in self._named_tids:
            return
        self._named_tids.add(tid)
        self._meta.append({"ph": "M", "ts": 0.0, "pid": self.pid,
                           "tid": tid, "name": "thread_name",
                           "args": {"name": name}})

    def instant(self, name: str, tid: int = 0, **args) -> None:
        """A zero-duration marker ("i" event, thread scope)."""
        self.events.append({"ph": "i", "ts": self.now_us(), "pid": self.pid,
                            "tid": tid, "name": name, "s": "t",
                            "args": args})

    def complete(self, name: str, ts_us: float, dur_us: float,
                 tid: int = 0, args: Optional[dict] = None) -> None:
        """An explicit "X" span — for spans whose interval is known after
        the fact (e.g. per-slot windows sharing the device-step interval).
        """
        self.events.append({"ph": "X", "ts": ts_us, "pid": self.pid,
                            "tid": tid, "name": name,
                            "dur": max(dur_us, 0.0), "args": args or {}})

    @contextmanager
    def span(self, name: str, tid: int = 0, **args):
        """Context-managed "X" span; emitted on exit so children are
        recorded (and end) before their parent."""
        t0 = self.now_us()
        try:
            yield
        finally:
            self.complete(name, t0, self.now_us() - t0, tid=tid, args=args)

    # -- export -------------------------------------------------------------

    def chrome_trace(self) -> dict:
        """The Chrome trace-event JSON object (``traceEvents`` form)."""
        return {"traceEvents": self._meta + list(self.events),
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> None:
        """Write the trace JSON (open in Perfetto / chrome://tracing)."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


#: nesting slack for recomputed span ends (float microseconds)
_ULP_US = 1e-3


def validate_chrome_trace(
        trace: Union[dict, Iterable[dict]]) -> List[dict]:
    """Validate trace-event schema; returns the event list.

    Checks the invariants the CI artifact consumers rely on:

    - every event is a JSON object carrying ``ph``, ``ts``, ``pid``,
      ``tid`` and ``name``, with a numeric ``ts``;
    - "X" events carry a numeric, non-negative ``dur``;
    - "C" (counter) events carry a non-empty ``args`` dict of numeric
      series — Perfetto silently drops malformed counters, so a schema
      bug here would otherwise pass validation and render as nothing;
    - within each ``(pid, tid)`` track, "X" spans strictly nest — no
      partial overlap (guaranteed by construction: a ``span()`` is
      emitted on exit, after every child has ended).  Instants and
      counters never participate in nesting, and a ring buffer that
      evicted a span's *parent* still validates: children are emitted
      (and evicted) before their parents, so any suffix of the event
      stream keeps the nesting invariant.

    Raises ``ValueError`` naming the first offending event.
    """
    if isinstance(trace, dict):
        events = trace.get("traceEvents")
        if not isinstance(events, list):
            raise ValueError("trace object has no traceEvents list")
    else:
        events = list(trace)

    def _numeric(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    tracks: Dict[tuple, List[dict]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            raise ValueError(
                f"event {i} is {type(ev).__name__}, not a trace-event "
                f"object")
        for field in ("ph", "ts", "pid", "tid", "name"):
            if field not in ev:
                raise ValueError(f"event {i} ({ev.get('name')!r}) missing "
                                 f"required field {field!r}")
        if not _numeric(ev["ts"]):
            raise ValueError(
                f"event {i} ({ev['name']!r}): ts must be a number, got "
                f"{ev['ts']!r}")
        if ev["ph"] == "X":
            dur = ev.get("dur")
            if not _numeric(dur) or dur < 0:
                raise ValueError(
                    f"event {i} ({ev['name']!r}): X event needs dur >= 0")
            tracks.setdefault((ev["pid"], ev["tid"]), []).append(ev)
        elif ev["ph"] == "C":
            args = ev.get("args")
            if not isinstance(args, dict) or not args:
                raise ValueError(
                    f"event {i} ({ev['name']!r}): C (counter) event needs "
                    f"a non-empty args dict of numeric series, got "
                    f"{args!r}")
            for series, v in args.items():
                if not _numeric(v):
                    raise ValueError(
                        f"event {i} ({ev['name']!r}): counter series "
                        f"{series!r} must be numeric, got {v!r}")
    for (pid, tid), spans in tracks.items():
        # sort children-inside-parents: by start, widest first on ties
        spans.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[dict] = []
        for ev in spans:
            end = ev["ts"] + ev["dur"]
            # spans store ts and dur separately, so a recomputed end can
            # pass the clock sample it was taken at by an ulp: a span that
            # starts (or ends) at that sample still follows (or nests)
            while stack and (ev["ts"] + _ULP_US
                             >= stack[-1]["ts"] + stack[-1]["dur"]):
                stack.pop()
            if stack and end > stack[-1]["ts"] + stack[-1]["dur"] + _ULP_US:
                raise ValueError(
                    f"track (pid={pid}, tid={tid}): span "
                    f"{ev['name']!r} [{ev['ts']}, {end}] partially "
                    f"overlaps {stack[-1]['name']!r} — spans must nest")
            stack.append(ev)
    return events


@contextmanager
def profiler_trace(trace_dir: Optional[str] = None):
    """Optional ``jax.profiler`` hook: bracket a run with a device-level
    trace when ``trace_dir`` is set; exact no-op when ``None``.

    The resulting TensorBoard/Perfetto trace carries the *device* view
    (kernel launches, transfers) that complements the host-side
    :class:`Tracer` timeline.
    """
    if not trace_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


#: device scopes (see :func:`scope`)
SCOPES = ("embed", "layers", "ssd", "attention", "rglru", "mlp", "moe",
          "moe_route", "unembed_loss", "mpx", "optimizer", "sampling")

#: the XLA frontend attribute :func:`scope` sets on the ops it encloses
SCOPE_ATTR = "repro_scope"

#: host spans the program opens on the profiler's clock
SPANS = ("data wait", "host sync", "checkpoint", "admit", "plan",
         "device step", "commit")


@contextmanager
def scope(name: str):
    """Tag the device work traced inside with ``name`` (one of
    :data:`SCOPES`): ``jax.named_scope(name)`` and the frontend
    attribute ``repro_scope=name``.  Nested scopes: the innermost wins.
    Transposed and rematerialized ops keep the scope of the forward op
    they came from."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; one of {SCOPES}")
    import jax
    from jax.experimental.xla_metadata import set_xla_metadata
    with jax.named_scope(name), set_xla_metadata(**{SCOPE_ATTR: name}):
        yield


def program_span(name: str):
    """A host span named ``name`` (one of :data:`SPANS`) on the
    profiler's timeline: a ``jax.profiler.TraceAnnotation``, a no-op
    when no profiler runs."""
    if name not in SPANS:
        raise ValueError(f"unknown program span {name!r}; one of {SPANS}")
    import jax
    return jax.profiler.TraceAnnotation(name)


class PhaseSpans:
    """Back-to-back program spans: :meth:`start` closes the open span
    and opens the next; leaving the ``with`` block closes the last, on
    any return or exception."""

    def __init__(self):
        self._open = None

    def __enter__(self) -> "PhaseSpans":
        return self

    def __exit__(self, *exc) -> None:
        self.end()

    def start(self, name: str) -> None:
        self.end()
        self._open = program_span(name)
        self._open.__enter__()

    def end(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
