"""repro.obs — unified telemetry: metrics registry, request tracing,
precision observability, and the serving flight recorder.

Four dependency-free layers instrumenting both halves of the stack:

- :mod:`~repro.obs.registry` — labeled counters / gauges /
  log2-bucketed histograms with ``snapshot()`` dicts, Prometheus text
  exposition, and JSON dumps.  :class:`repro.serve.EngineStats` is built
  on it (its ``summary()`` schema unchanged), and the serving engine,
  scheduler and paged cache report queue depth, admissions, page-pool
  occupancy/high-watermark and speculative truncations into it.
- :mod:`~repro.obs.trace` — a span/event :class:`Tracer` (injectable
  clock, bounded ring buffer) exporting Chrome trace-event JSON: a serve
  session renders in Perfetto as per-slot request timelines (submit →
  admit → prefill chunks → decode/spec windows with accept counts →
  truncate → retire) over an engine-phase track (plan / device step /
  host sync / commit).  For the ``jax.profiler`` timeline:
  :func:`~repro.obs.trace.scope` tags device work with a named scope
  and the ``repro_scope`` frontend attribute (``embed``, ``layers``,
  ``ssd`` / ``attention`` / ``rglru`` / ``mlp`` / ``moe``,
  ``unembed_loss``, ``mpx``, ``optimizer``, ``sampling``), which a TPU
  trace prints in each op's name; :func:`~repro.obs.trace.program_span`
  opens host spans on the profiler's clock (``data wait``, ``host
  sync``, ``checkpoint`` in each ``train`` step; the engine's ``admit``
  / ``plan`` / ``device step`` / ``host sync`` / ``commit`` in each
  ``serve`` step); and
  :func:`~repro.obs.trace.profiler_trace` is the optional trace-dir
  hook.
- :mod:`~repro.obs.precision` — the MPX §3.3 signals:
  :class:`PrecisionStats` (loss-scale trajectory, overflow/skip-step
  counters, halving/doubling events) and
  :func:`~repro.obs.precision.per_layer_grad_summary`, per-layer grad
  amax / nonfinite / underflow fractions computed *inside* the jitted
  train step as fixed-shape arrays — no host callbacks.
- :mod:`~repro.obs.journal` — the **flight recorder**:
  :class:`JournalRecorder` event-sources every external input to a
  ``ServeEngine`` drive (config fingerprint, fault schedule, clock
  samples, submits/cancels, per-tick digests with a rolling token hash)
  into bounded append-only JSONL; :func:`replay_journal` reconstructs
  the engine and re-drives it deterministically, naming the first
  divergent tick on mismatch.  :mod:`~repro.obs.postmortem` joins the
  journal with the other three layers' artifacts into a per-request
  incident report (``python -m repro.obs.postmortem``).

Everything here is host-side bookkeeping recorded around the jitted
steps; tracing or journaling a serve session adds zero device syncs to
``ServeEngine.step()`` (pinned by tests) and <3% tok/s on the bench
workload (the ``serving_obs_overhead_pct`` and
``serving_journal_overhead_pct`` CI rows).
"""
from repro.obs.journal import (JournalDivergence, JournalError,
                               JournalMismatch, JournalRecorder,
                               JournalTruncated, ReplayReport,
                               read_journal, replay_journal)
from repro.obs.registry import (Counter, Gauge, Histogram, Registry,
                                merged_prometheus, merged_snapshot)
from repro.obs.trace import (SCOPES, SPANS, PhaseSpans, Tracer,
                             profiler_trace, program_span, scope,
                             validate_chrome_trace)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "JournalDivergence",
    "JournalError",
    "JournalMismatch",
    "JournalRecorder",
    "JournalTruncated",
    "PhaseSpans",
    "PrecisionStats",
    "Registry",
    "ReplayReport",
    "SCOPES",
    "SPANS",
    "Tracer",
    "grad_layer_names",
    "merged_prometheus",
    "merged_snapshot",
    "per_layer_grad_summary",
    "profiler_trace",
    "program_span",
    "read_journal",
    "replay_journal",
    "scope",
    "validate_chrome_trace",
]


def __getattr__(name):
    # precision imports jax; keep `import repro.obs` free of that cost
    # for stdlib-only consumers (registry/trace never touch jax)
    if name in ("PrecisionStats", "per_layer_grad_summary",
                "grad_layer_names"):
        from repro.obs import precision
        return getattr(precision, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
