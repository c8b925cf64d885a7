"""repro.obs: registry / tracer / precision telemetry, and the serving
engine's observability contracts.

Pins the load-bearing invariants of the telemetry layer:

- the metrics registry's Prometheus subset (label series, monotone
  counters, log2 bucket boundaries, text exposition);
- the Chrome-trace schema the CI artifact relies on (required fields,
  span nesting) — validated on a real engine drive, no bench run needed;
- ``EngineStats.summary()``'s exact pre-registry key set (the bench/CI
  artifact schema keys on it);
- ``_percentile`` nearest-rank edge cases;
- the full-tick timing contract of ``ServeEngine.step()`` (elapsed
  covers admit through commit ≈ drain wall time);
- the ``drain()`` no-progress guard;
- **zero added device syncs**: instrumentation on/off, one engine step
  transfers exactly the two ``(B,)`` arrays it always has;
- the §3.3 precision trajectory: overflow -> halve -> skip observable in
  a :class:`PrecisionStats` snapshot, per-layer grad summaries computed
  in-jit with fixed shapes.
"""
import inspect
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import mpx, serve
from repro.configs.base import ModelConfig, RunConfig
from repro.core.loss_scaling import DynamicLossScaling
from repro.models import transformer as T
from repro.obs import Registry, Tracer, validate_chrome_trace
from repro.obs.precision import (FP16_TINY, PrecisionStats,
                                 grad_layer_names, per_layer_grad_summary)
from repro.obs.registry import Counter, Gauge, Histogram
from repro.serve.metrics import _percentile
from repro.serve.scheduler import Request

CFG = ModelConfig(
    name="obs-test", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, pattern=("attn",), mlp="swiglu",
    tie_embeddings=True, remat="none",
)


@pytest.fixture(scope="module")
def params():
    return mpx.cast_to_bfloat16(T.init_params(jax.random.key(0), CFG))


def ragged_prompts(n, seed=0, lo=2, hi=14):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, int(k)).tolist()
            for k in rng.integers(lo, hi, n)]


# --------------------------------------------------------------------------
# registry: counters / gauges / histograms
# --------------------------------------------------------------------------

def test_counter_label_series():
    c = Counter("steps_total", "x", labels=("kind",))
    c.inc(kind="prefill")
    c.inc(2, kind="mixed")
    c.inc(kind="mixed")
    assert c.value(kind="prefill") == 1
    assert c.value(kind="mixed") == 3
    assert c.value(kind="decode") == 0          # untouched series reads 0
    assert c.total == 4
    with pytest.raises(ValueError):             # counters only go up
        c.inc(-1, kind="mixed")
    with pytest.raises(ValueError):             # undeclared label
        c.inc(flavor="x")


def test_gauge_set_and_ratchet():
    g = Gauge("pages_used_peak")
    g.set(5)
    g.set(3)
    assert g.value() == 3
    g.set_max(7)
    g.set_max(2)                                # ratchet: never goes down
    assert g.value() == 7


def test_histogram_bucket_boundaries():
    h = Histogram("lat", lo_exp=0, hi_exp=3)    # edges 1, 2, 4, 8, +Inf
    assert h.edges == (1.0, 2.0, 4.0, 8.0, float("inf"))
    assert h.bucket_index(0.5) == 0
    assert h.bucket_index(1.0) == 0             # le semantics: v <= edge
    assert h.bucket_index(2.0) == 1
    assert h.bucket_index(2.0000001) == 2
    assert h.bucket_index(8.0) == 3
    assert h.bucket_index(8.1) == 4             # +Inf bucket
    assert h.bucket_index(0.0) == 0             # non-positive clamps low
    assert h.bucket_index(-3.0) == 0


def test_histogram_exact_on_powers_of_two():
    h = Histogram("wide", lo_exp=-20, hi_exp=4)
    for i, e in enumerate(range(-20, 5)):
        v = 2.0 ** e
        assert h.bucket_index(v) == i, f"2**{e} landed off its edge"
        assert h.bucket_index(v * (1 + 1e-9)) == i + 1


def test_histogram_observe_count_sum_cumulative():
    h = Histogram("lat", lo_exp=0, hi_exp=2)    # edges 1, 2, 4, +Inf
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.count() == 4
    assert h.sum() == pytest.approx(105.0)
    assert h.buckets() == [(1.0, 1), (2.0, 2), (4.0, 3), (float("inf"), 4)]


def test_registry_get_or_create_and_kind_mismatch():
    r = Registry()
    a = r.counter("x_total", labels=("k",))
    b = r.counter("x_total", labels=("k",))
    assert a is b                               # shared by name
    with pytest.raises(ValueError):             # same name, different kind
        r.gauge("x_total")
    with pytest.raises(ValueError):             # same kind, other labels
        r.counter("x_total", labels=("other",))


def test_registry_snapshot_and_prometheus():
    r = Registry()
    r.counter("ticks_total", "ticks", labels=("kind",)).inc(3, kind="mixed")
    r.gauge("depth", "queue").set(2)
    h = r.histogram("gap_seconds", "itl", lo_exp=-2, hi_exp=0)
    h.observe(0.3)
    h.observe(0.9)
    snap = r.snapshot()
    assert snap['ticks_total{kind="mixed"}'] == 3
    assert snap["depth"] == 2
    assert snap["gap_seconds_count"] == 2
    assert snap["gap_seconds_sum"] == pytest.approx(1.2)
    assert snap['gap_seconds_bucket{le="+Inf"}'] == 2
    prom = r.prometheus()
    assert "# TYPE ticks_total counter" in prom
    assert 'ticks_total{kind="mixed"} 3' in prom
    assert "# TYPE gap_seconds histogram" in prom
    assert 'gap_seconds_bucket{le="0.5"} 1' in prom
    assert 'gap_seconds_bucket{le="+Inf"} 2' in prom
    assert "gap_seconds_count 2" in prom
    # json round-trips the snapshot
    assert json.loads(r.json_dump()) == snap


def test_prometheus_escapes_hostile_label_values():
    """Exposition escaping: a label value carrying backslashes, quotes,
    or newlines must not corrupt the scrape document."""
    r = Registry()
    c = r.counter("errors_total", "why", labels=("msg",))
    c.inc(msg='path "C:\\tmp"\nsecond line')
    prom = r.prometheus()
    # one sample line (the newline is escaped, not emitted raw)...
    samples = [ln for ln in prom.splitlines() if not ln.startswith("#")]
    assert len(samples) == 1
    # ...with the exposition-format escapes, backslash escaped first
    assert ('errors_total{msg="path \\"C:\\\\tmp\\"\\nsecond line"} 1'
            == samples[0])
    # HELP text escapes backslash + newline too
    r2 = Registry()
    r2.counter("x_total", "line one\nline \\two").inc()
    help_line = r2.prometheus().splitlines()[0]
    assert help_line == "# HELP x_total line one\\nline \\\\two"


def test_merged_prometheus_one_header_per_shared_family():
    """Registries sharing a metric family merge under a single
    HELP/TYPE header — Prometheus rejects duplicate family headers."""
    from repro.obs import merged_prometheus
    a, b = Registry(), Registry()
    a.counter("shared_total", "shared fam", labels=("src",)).inc(src="a")
    b.counter("shared_total", "shared fam", labels=("src",)).inc(2, src="b")
    a.gauge("only_a").set(1)
    b.gauge("only_b").set(2)
    prom = merged_prometheus(a, b)
    lines = prom.splitlines()
    assert lines.count("# TYPE shared_total counter") == 1
    assert lines.count("# HELP shared_total shared fam") == 1
    # both registries' series survive the merge
    assert 'shared_total{src="a"} 1' in lines
    assert 'shared_total{src="b"} 2' in lines
    assert "only_a 1" in lines and "only_b 2" in lines
    # a name that changes kind across registries is a schema bug
    c = Registry()
    c.gauge("shared_total")
    with pytest.raises(ValueError, match="one family name, one type"):
        merged_prometheus(a, c)


# --------------------------------------------------------------------------
# tracer + chrome-trace schema
# --------------------------------------------------------------------------

def _fake_clock(step_s=0.001):
    t = [0.0]

    def clock():
        t[0] += step_s
        return t[0]

    return clock


def test_tracer_spans_nest_and_validate():
    tr = Tracer(clock=_fake_clock())
    tr.thread_name(1, "slot 0")
    with tr.span("tick", tid=0):
        with tr.span("device step", tid=0):
            tr.instant("mark", tid=1, rid=7)
    events = validate_chrome_trace(tr.chrome_trace())
    names = [e["name"] for e in events]
    assert "process_name" in names and "thread_name" in names
    spans = [e for e in events if e["ph"] == "X"]
    # emitted on exit: child first, and strictly inside the parent
    assert [e["name"] for e in spans] == ["device step", "tick"]
    child, parent = spans
    assert parent["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    instant = next(e for e in events if e["ph"] == "i")
    assert instant["args"] == {"rid": 7}


def test_tracer_ring_buffer_bounded_keeps_meta():
    tr = Tracer(clock=_fake_clock(), max_events=4)
    tr.thread_name(1, "slot 0")
    for i in range(10):
        tr.instant(f"e{i}")
    trace = tr.chrome_trace()
    non_meta = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    assert len(non_meta) == 4                       # oldest evicted
    assert [e["name"] for e in non_meta] == ["e6", "e7", "e8", "e9"]
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len(meta) == 2                           # process + thread names


def test_validate_rejects_missing_fields_and_overlap():
    with pytest.raises(ValueError, match="missing required field"):
        validate_chrome_trace([{"ph": "i", "ts": 0, "pid": 0, "tid": 0}])
    with pytest.raises(ValueError, match="needs dur"):
        validate_chrome_trace(
            [{"ph": "X", "ts": 0, "pid": 0, "tid": 0, "name": "x"}])
    overlap = [
        {"ph": "X", "ts": 0, "dur": 10, "pid": 0, "tid": 0, "name": "a"},
        {"ph": "X", "ts": 5, "dur": 10, "pid": 0, "tid": 0, "name": "b"},
    ]
    with pytest.raises(ValueError, match="must nest"):
        validate_chrome_trace(overlap)
    # same intervals on different tracks are fine
    overlap[1]["tid"] = 1
    validate_chrome_trace(overlap)


def test_validate_rejects_malformed_event_shapes():
    """Non-object events and non-numeric timestamps get actionable
    errors, not KeyError/TypeError."""
    with pytest.raises(ValueError, match="not a trace-event object"):
        validate_chrome_trace([["ph", "i"]])
    with pytest.raises(ValueError, match="ts must be a number"):
        validate_chrome_trace(
            [{"ph": "i", "ts": "0", "pid": 0, "tid": 0, "name": "x"}])
    with pytest.raises(ValueError, match="needs dur"):
        validate_chrome_trace(
            [{"ph": "X", "ts": 0, "dur": "5", "pid": 0, "tid": 0,
              "name": "x"}])
    with pytest.raises(ValueError, match="needs dur"):
        validate_chrome_trace(
            [{"ph": "X", "ts": 0, "dur": -1, "pid": 0, "tid": 0,
              "name": "x"}])


def test_validate_counter_events():
    """C events need a non-empty dict of numeric series; a valid counter
    mixed with instants and spans passes."""
    base = {"ph": "C", "ts": 1, "pid": 0, "tid": 0, "name": "pool"}
    with pytest.raises(ValueError, match="non-empty args dict"):
        validate_chrome_trace([dict(base)])                 # args missing
    with pytest.raises(ValueError, match="non-empty args dict"):
        validate_chrome_trace([dict(base, args={})])        # args empty
    with pytest.raises(ValueError, match="must be numeric"):
        validate_chrome_trace([dict(base, args={"free": "3"})])
    with pytest.raises(ValueError, match="must be numeric"):
        validate_chrome_trace([dict(base, args={"free": True})])
    mixed = [
        {"ph": "X", "ts": 0, "dur": 10, "pid": 0, "tid": 0, "name": "tick"},
        {"ph": "i", "ts": 2, "pid": 0, "tid": 0, "name": "admit", "s": "t"},
        dict(base, args={"free": 3, "used": 2.5}),
        {"ph": "X", "ts": 4, "dur": 2, "pid": 0, "tid": 0, "name": "plan"},
    ]
    assert len(validate_chrome_trace(mixed)) == 4


def test_validate_ring_evicted_parent_still_nests():
    """A ring buffer evicts children before parents (spans are emitted
    on exit), so an orphaned tail of the stream must still validate."""
    tr = Tracer(clock=_fake_clock(), max_events=3)
    with tr.span("tick"):
        with tr.span("plan"):
            pass
        with tr.span("device step"):
            pass
        with tr.span("commit"):
            pass
    # 4 spans through a 3-slot ring: "plan" (oldest child) evicted, the
    # surviving suffix has "tick" without one of its children
    events = [e for e in tr.chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert len(events) == 3 and events[-1]["name"] == "tick"
    validate_chrome_trace(tr.chrome_trace())


def test_validate_back_to_back_spans_whose_end_rounds_up():
    """A span recorded as (ts, end - ts) can recompute to an end an ulp
    past the sample it was closed at (here a long first device step, the
    compile, early in the trace); the span starting at that sample
    follows it and must not read as a partial overlap."""
    dev, sync, end = 109039.952957, 1755523.795284, 1783000.0
    assert dev + (sync - dev) > sync
    tr = Tracer(clock=_fake_clock())
    tr.complete("tick", dev - 10.0, end - dev + 20.0)
    tr.complete("device step", dev, sync - dev)
    tr.complete("host sync", sync, end - sync)
    validate_chrome_trace(tr.chrome_trace())


# --------------------------------------------------------------------------
# _percentile nearest-rank edges + summary schema pin
# --------------------------------------------------------------------------

def test_percentile_nearest_rank_edges():
    assert _percentile([42.0], 0.0) == 42.0         # len-1: any q
    assert _percentile([42.0], 0.5) == 42.0
    assert _percentile([42.0], 1.0) == 42.0
    vals = [1.0, 2.0, 3.0, 4.0]
    assert _percentile(vals, 0.25) == 1.0           # exact boundary q
    assert _percentile(vals, 0.5) == 2.0
    assert _percentile(vals, 0.75) == 3.0
    assert _percentile(vals, 1.0) == 4.0
    assert _percentile(vals, 0.51) == 3.0           # just past a boundary
    assert _percentile([4.0, 1.0, 3.0, 2.0], 0.5) == 2.0   # unsorted input


def test_engine_stats_summary_schema_pinned():
    """summary() keys predate the registry refactor — pinned verbatim."""
    st = serve.EngineStats(2)
    st.record_step("prefill", 1, 0, 0.01,
                   prefill_tokens=[4, 0], decode_tokens=[0, 0])
    st.record_step("mixed", 2, 2, 0.01,
                   prefill_tokens=[0, 3], decode_tokens=[1, 0],
                   proposed=2, accepted=1)
    st.record_token_gap(0.005)
    rm = serve.RequestMetrics(request_id=0, prompt_len=4, submit_time=0.0,
                              first_token_time=0.01, last_token_time=0.02,
                              finish_time=0.02)
    rm.new_tokens = 2
    st.record_finish(rm)
    s = st.summary()
    assert set(s) == {
        "requests", "steps", "prefill_steps", "decode_steps", "mixed_steps",
        "new_tokens", "prompt_tokens", "prefill_tokens_fed",
        "decode_tokens_fed", "elapsed_s", "tok_per_s", "tokens_per_step",
        "mean_occupancy", "spec_proposed", "spec_accepted",
        "spec_accept_rate", "ttft_mean_s", "ttft_p95_s",
        "itl_p50_s", "itl_p95_s", "itl_mean_s"}
    # the legacy attribute API reads through the registry
    assert st.steps == 2 and st.prefill_steps == 1 and st.mixed_steps == 1
    assert st.slot_prefill_tokens == [4, 3]
    assert st.slot_decode_tokens == [1, 0]
    assert s["prefill_tokens_fed"] == 7.0 and s["decode_tokens_fed"] == 1.0
    assert s["spec_accept_rate"] == 0.5
    # prometheus export carries the same numbers
    prom = st.registry.prometheus()
    assert 'serve_steps_total{kind="mixed"} 1' in prom
    assert 'serve_slot_tokens_total{slot="0",kind="prefill"} 4' in prom
    assert "serve_itl_seconds_count 1" in prom
    # a fresh instance is fully reset (the bench's warmup discard)
    assert serve.EngineStats(2).steps == 0


# --------------------------------------------------------------------------
# engine: trace schema on a real drive, timing, no-progress, zero syncs
# --------------------------------------------------------------------------

@pytest.mark.serve
def test_engine_trace_schema_and_registry_exports(params):
    """Fast trace-schema check: a tiny drive, no bench run needed."""
    tracer = Tracer()
    engine = serve.ServeEngine(CFG, params, n_slots=2, max_seq=64,
                               page_size=16, chunk_size=16, tracer=tracer)
    for p in ragged_prompts(3):
        engine.submit(p, max_new=4)
    results = engine.drain()
    assert len(results) == 3
    events = validate_chrome_trace(tracer.chrome_trace())
    names = {e["name"] for e in events}
    for want in ("submit", "admit", "plan", "device step", "host sync",
                 "commit", "tick", "prefill", "decode", "retire"):
        assert want in names, f"lifecycle event {want!r} missing"
    # slot spans live on per-slot tracks, engine phases on tid 0
    assert {e["tid"] for e in events if e["name"] == "decode"} <= {1, 2}
    assert {e["tid"] for e in events if e["name"] == "tick"} == {0}
    # registry exports: queue drained, pages back in the pool, peak kept
    snap = engine.metrics_snapshot()
    assert snap["serve_queue_depth"] == 0
    assert snap["serve_busy_slots"] == 0
    assert snap["serve_pages_used"] == 0
    assert snap["serve_pages_used_peak"] > 0
    assert snap["serve_admissions_total"] == 3
    assert snap["serve_requests_finished_total"] == 3
    prom = engine.prometheus()
    assert "serve_queue_depth" in prom and "serve_steps_total" in prom


@pytest.mark.serve
def test_prefix_cache_metrics_schema_pinned(params):
    """Sharing-layer observability: the hit/miss/COW counters and the
    shared/cached page gauges exist from tick zero (zero-valued series,
    not absent) and export in both the snapshot and Prometheus text."""
    engine = serve.ServeEngine(CFG, params, n_slots=2, max_seq=64,
                               page_size=16, chunk_size=16,
                               prefix_cache=True)
    # pinned at construction, before any traffic: a dashboard must see
    # the series immediately, not after the first hit
    snap = engine.metrics_snapshot()
    for name in ("serve_prefix_hits_total", "serve_prefix_miss_total",
                 "serve_cow_copies_total"):
        assert snap[name] == 0
    assert snap["serve_pages_shared"] == 0
    assert snap["serve_pages_cached"] == 0
    prompt = list(range(1, 33))              # 2 full pages
    engine.submit(prompt, max_new=4)
    engine.drain()
    engine.submit(list(prompt), max_new=4)   # identical: full-page hit
    engine.drain()
    snap = engine.metrics_snapshot()
    assert snap["serve_prefix_hits_total"] == 2
    assert snap["serve_prefix_miss_total"] >= 1
    assert snap["serve_cow_copies_total"] == 1    # the boundary COW
    assert snap["serve_pages_cached"] > 0         # parked after retire
    prom = engine.prometheus()
    for name in ("serve_prefix_hits_total", "serve_prefix_miss_total",
                 "serve_cow_copies_total", "serve_pages_shared",
                 "serve_pages_cached"):
        assert name in prom, f"{name} missing from Prometheus export"
    # the per-request view: cached_prefix_tokens rides RequestMetrics
    res = engine.drain()
    assert res[1].metrics.cached_prefix_tokens == len(prompt) - 1


@pytest.mark.serve
def test_engine_step_transfers_exactly_two_arrays(monkeypatch, params,
                                                  tmp_path):
    """Zero added device syncs: with tracer and/or journal enabled, one
    engine step crosses device->host exactly twice (the (B,) accept and
    token arrays the verifier always produces)."""
    from repro.obs import JournalRecorder
    import repro.serve.engine as eng

    class CountingNp:
        def __init__(self, real):
            self._real = real
            self.asarray_calls = 0

        def __getattr__(self, name):
            return getattr(self._real, name)

        def asarray(self, *a, **k):
            self.asarray_calls += 1
            return self._real.asarray(*a, **k)

    proxy = CountingNp(np)
    monkeypatch.setattr(eng, "np", proxy)
    counts = {}
    variants = (
        ("off", None, None),
        ("tracer", Tracer(), None),
        ("journal", None, JournalRecorder(str(tmp_path / "pin.jsonl"))),
    )
    for label, tracer, journal in variants:
        engine = serve.ServeEngine(CFG, params, n_slots=2, max_seq=64,
                                   page_size=16, chunk_size=16,
                                   tracer=tracer, journal=journal)
        engine.submit([1, 2, 3], max_new=3)
        per_step = []
        while engine.scheduler.has_work:
            before = proxy.asarray_calls
            engine.step()
            per_step.append(proxy.asarray_calls - before)
        counts[label] = per_step
        if journal is not None:
            journal.close()
        assert all(n == 2 for n in per_step), (label, per_step)
    assert counts["tracer"] == counts["off"]
    assert counts["journal"] == counts["off"]


def test_no_blocking_sync_in_serve_hot_path_sources():
    """block_until_ready must not appear in the serving hot path — the
    only intentional transfer points are the two np.asarray calls in
    engine.step() (counted above)."""
    import repro.serve.cache
    import repro.serve.engine
    import repro.serve.metrics
    import repro.serve.scheduler
    for mod in (repro.serve.engine, repro.serve.scheduler,
                repro.serve.cache, repro.serve.metrics):
        assert "block_until_ready" not in inspect.getsource(mod), mod


@pytest.mark.serve
def test_step_elapsed_covers_full_tick(params):
    """Regression: EngineStats.elapsed must cover admit through commit.
    Slow both phases down; the recorded elapsed must absorb the delays
    and stay ~= the drain() wall time."""
    engine = serve.ServeEngine(CFG, params, n_slots=2, max_seq=64,
                               page_size=16, chunk_size=16)
    engine.submit([1, 2, 3], max_new=2)          # warm the compiled step
    engine.drain()
    engine.stats = serve.EngineStats(2)

    delay = 0.005
    real_admit, real_commit = engine.scheduler.admit, engine.scheduler.commit

    def slow_admit(*a, **k):
        time.sleep(delay)
        return real_admit(*a, **k)

    def slow_commit(*a, **k):
        time.sleep(delay)
        return real_commit(*a, **k)

    engine.scheduler.admit = slow_admit
    engine.scheduler.commit = slow_commit
    for p in ragged_prompts(3):
        engine.submit(p, max_new=4)
    t0 = time.perf_counter()
    engine.drain()
    wall = time.perf_counter() - t0
    st = engine.stats
    assert st.steps > 0
    # each recorded tick ran one slowed admit and one slowed commit; the
    # pre-fix timing (t0 after admit, stop before commit) missed both
    assert st.elapsed >= st.steps * 2 * delay * 0.95
    assert st.elapsed <= wall * 1.01
    assert st.elapsed >= 0.6 * wall


@pytest.mark.serve
def test_drain_no_progress_guard_names_stuck_requests(params):
    """A request too large for the pool that bypassed submit() validation
    must raise an actionable error, not spin drain() forever."""
    engine = serve.ServeEngine(CFG, params, n_slots=2, max_seq=32,
                               page_size=16)
    # bypass submit()'s pool-fit validation: enqueue directly
    engine.scheduler.waiting.append(Request(99, [1] * 8, max_new=1000))
    with pytest.raises(RuntimeError, match=r"no progress.*\[99\]"):
        engine.drain()


# --------------------------------------------------------------------------
# precision telemetry: §3.3 trajectory + in-jit per-layer summaries
# --------------------------------------------------------------------------

def test_precision_stats_trajectory_halve_and_double():
    scaling = DynamicLossScaling(2.0 ** 15, period=2)
    ps = PrecisionStats()
    ps.record_scaling(0, scaling)
    scaling = scaling.adjust(jnp.asarray(False))       # overflow -> halve
    ps.record_scaling(1, scaling, grads_finite=False)
    for step in (2, 3):                                # period=2 -> double
        scaling = scaling.adjust(jnp.asarray(True))
        ps.record_scaling(step, scaling)
    assert ps.steps == 4
    assert ps.overflow_steps == 1
    assert ps.scale_halvings == 1
    assert ps.scale_doublings == 1
    snap = ps.snapshot()
    assert snap['train_loss_scale_events_total{event="halved"}'] == 1
    assert snap['train_loss_scale_events_total{event="doubled"}'] == 1
    traj = snap["loss_scale_trajectory"]
    assert [s for s, _ in traj] == [0, 1, 2, 3]
    assert traj[1][1] == traj[0][1] / 2                # the halving
    assert traj[3][1] == traj[1][1] * 2                # the recovery
    assert snap["train_loss_scale"] == traj[-1][1]


def test_fp16_overflow_halving_observable_in_snapshot():
    """End to end at fp16: a deliberately oversized scale overflows the
    gradients, the controller halves, the skip is counted — the
    quickstart's observable §3.3 loop, in miniature."""
    mpx.set_half_dtype(jnp.float16)
    try:
        w = {"w": jnp.ones((8, 8), jnp.float32)}
        batch = {"x": jnp.full((4, 8), 3.0), "y": jnp.zeros((4, 8))}

        def loss_fn(m, b):
            pred = b["x"] @ m["w"]
            return mpx.force_full_precision(jnp.mean)((pred - b["y"]) ** 2)

        scaling = mpx.DynamicLossScaling(2.0 ** 24, period=100)
        ps = PrecisionStats()
        ps.record_scaling(0, scaling)
        for step in range(3):
            scaling, finite, _ = mpx.filter_grad(loss_fn, scaling)(w, batch)
            ps.record_scaling(step + 1, scaling, bool(finite))
        assert ps.overflow_steps >= 1
        assert ps.scale_halvings >= 1
        snap = ps.snapshot()
        assert snap['train_loss_scale_events_total{event="halved"}'] >= 1
        traj = snap["loss_scale_trajectory"]
        assert traj[-1][1] < traj[0][1]
    finally:
        mpx.set_half_dtype(jnp.bfloat16)


def test_per_layer_grad_summary_values_in_jit():
    grads = {"a": jnp.asarray([1.0, -4.0, 0.0, jnp.inf]),
             "b": jnp.asarray([2.0 ** -20, 1.0]),
             "c": jnp.asarray([1, 2], jnp.int32)}      # int leaf excluded
    names = grad_layer_names(grads)
    assert names == ["a", "b"]
    out = jax.jit(per_layer_grad_summary)(grads)
    amax = np.asarray(out["grad_amax_per_layer"])
    nonf = np.asarray(out["grad_nonfinite_frac_per_layer"])
    under = np.asarray(out["grad_underflow_frac_per_layer"])
    assert amax.shape == nonf.shape == under.shape == (2,)
    assert np.isinf(amax[0]) and amax[1] == 1.0
    assert nonf[0] == pytest.approx(0.25) and nonf[1] == 0.0
    # leaf b: two nonzero elements, one below fp16's smallest normal
    assert 2.0 ** -20 < FP16_TINY
    assert under[0] == 0.0 and under[1] == pytest.approx(0.5)


def test_per_layer_summary_handles_all_zero_leaf():
    out = per_layer_grad_summary({"z": jnp.zeros(4)})
    assert float(out["grad_underflow_frac_per_layer"][0]) == 0.0  # not NaN
    assert float(out["grad_amax_per_layer"][0]) == 0.0


def test_record_layer_summary_exports_labeled_gauges():
    ps = PrecisionStats()
    ps.record_layer_summary(
        ["l0", "l1"],
        {"grad_amax_per_layer": np.asarray([0.5, 2.0]),
         "grad_underflow_frac_per_layer": np.asarray([0.0, 0.25])})
    snap = ps.snapshot()
    assert snap["grad_layer_names"] == ["l0", "l1"]
    assert snap['grad_amax{layer="l1"}'] == 2.0
    assert snap['grad_underflow_frac{layer="l1"}'] == 0.25
    assert snap["grad_amax_per_layer"] == [0.5, 2.0]
    with pytest.raises(ValueError, match="layer names"):
        ps.record_layer_summary(["l0"], {"grad_amax_per_layer": [1.0, 2.0]})


def test_train_step_grad_stats_rides_metrics_dict():
    """grad_stats=True adds fixed-shape (L,) arrays to the jitted step's
    metrics — no host callback, same compiled program shape."""
    from repro.optim import adamw
    from repro.train.steps import make_train_step

    run = RunConfig(policy="p=f32,c=f32,o=f32", zero1=False,
                    master_weights="none")

    def loss_fn(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2), {}

    optimizer = adamw(learning_rate=1e-2)
    params_tree = {"w": jnp.ones((4, 4)) * 0.1}
    state = {"params": params_tree,
             "opt_state": optimizer.init(params_tree),
             "scaling": DynamicLossScaling(2.0 ** 10),
             "step": jnp.zeros((), jnp.int32)}
    step_fn = jax.jit(make_train_step(CFG, run, optimizer, loss_fn=loss_fn,
                                      grad_stats=True))
    batch = {"x": jnp.ones((2, 4))}
    _, metrics = step_fn(state, batch)
    names = grad_layer_names(params_tree)
    for key in ("grad_amax_per_layer", "grad_nonfinite_frac_per_layer",
                "grad_underflow_frac_per_layer"):
        assert metrics[key].shape == (len(names),)
    assert float(metrics["grad_nonfinite_frac_per_layer"][0]) == 0.0
    assert float(metrics["grad_amax_per_layer"][0]) > 0.0


def test_serving_obs_overhead_row_registered():
    """The bench's tracing-overhead row is part of the pinned schema."""
    from benchmarks.serving_bench import expected_row_names
    assert "serving_obs_overhead_pct" in expected_row_names()


def test_serving_journal_overhead_row_registered():
    """The flight-recorder overhead row is part of the pinned schema."""
    from benchmarks.serving_bench import expected_row_names
    assert "serving_journal_overhead_pct" in expected_row_names()


@pytest.mark.serve
def test_request_phase_histograms_exported(params):
    """A drive populates the per-request phase histograms
    (queue wait / prefill / decode) in the engine's registry."""
    engine = serve.ServeEngine(CFG, params, n_slots=2, max_seq=64,
                               page_size=16, chunk_size=16)
    for p in ragged_prompts(3):
        engine.submit(p, max_new=4)
    results = engine.drain()
    assert len(results) == 3
    prom = engine.stats.registry.prometheus()
    for fam in ("serve_queue_wait_seconds", "serve_prefill_seconds",
                "serve_decode_seconds"):
        assert f"# TYPE {fam} histogram" in prom
        assert f"{fam}_count 3" in prom
    # phases decompose: queue_wait + prefill + decode <= total latency
    for r in results:
        m = r.metrics
        assert m.queue_wait >= 0.0
        assert m.prefill_seconds >= 0.0
        assert m.decode_seconds >= 0.0
        total = m.finish_time - m.submit_time
        assert (m.queue_wait + m.prefill_seconds + m.decode_seconds
                <= total + 1e-9)
