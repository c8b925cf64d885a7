"""The ServeEngine facade: submit() / step() / drain().

Ties the subsystem together: the per-layer-kind paged state pool (paged
KV pools for attention layers, O(1) per-slot fp32 state for rglru/ssd
layers — one host allocator for both), the mixed-chunk
continuous-batching scheduler (host plans), ONE jitted
``(B, chunk_size)`` specialization of the unified ``serve_forward`` step
— every tick is a mixed plan in which each active slot contributes
either a prefill chunk or its decode *window* — and fp32
verification/sampling over each slot's window logits.  One engine serves
attn, ssm, rglru and hybrid stacks; greedy output is token-identical to
the dense per-token ``decode()`` oracle for all of them.  Speculative
windows require the rollback only paged KV supports, so recurrent and
hybrid stacks must run with ``spec_tokens=0`` (refused with an
actionable error at construction).

Speculative decoding (``spec_tokens > 0``) turns the decode side of every
tick into a propose/verify/commit loop: a host-side
:class:`~repro.serve.propose.Proposer` (n-gram prompt lookup by default)
drafts up to ``spec_tokens`` tokens per decoding slot, the scheduler packs
committed-token + drafts into the slot's chunk columns, ``serve_forward``
returns per-position logits for the whole window (``logit_idx`` gather),
and :func:`repro.serve.sampling.rejection_sample` accepts the longest
matching prefix plus one corrected/bonus token — so one engine step can
emit up to ``spec_tokens + 1`` tokens per slot.  ``commit()`` rolls each
slot's cache length back over the rejected tail
(:meth:`PagedKVCache.truncate`); with temperature 0 the accept rule is
argmax equality, making the speculative engine token-identical to the
non-speculative one.  ``spec_tokens = 0`` is the same compiled program
shape with a 1-wide window — plain decoding.

When ``use_kernel`` is set, EVERY step — prefill, decode and mixed alike —
routes attention through the Pallas paged-attention kernel
(``repro.kernels.paged_attention``): the page table is a scalar-prefetch
operand and the kernel streams each slot's allocated pages straight from
the shared pools (``pages_per_block`` logical pages per K-block), so the
per-step gathered dense copy of the cache never exists and there is still
exactly one compiled step program.

Precision: params are expected pre-cast to the serving dtype (bf16); the
KV pages store in the ``kv_dtype`` policy format (bf16 passthrough, or
int8 / fp8 with per-page amax scales dequantized inside the kernel —
``repro.quant``); softmax inside the model, the sampling transforms and
the rejection-sampling accept/residual rule are fp32 — the inference half
of the MPX discipline (verification shares softmax's "known-fragile"
status: a bf16 tail probability flips accept decisions).  ``kv_dtype``
accepts the format name, a :class:`~repro.quant.KVFormat`, or a
:class:`~repro.core.policy.Policy` (its ``kv=`` component), so
``ServeEngine(cfg, params, kv_dtype=Policy.parse("p=f32,c=bf16,o=bf16,
kv=i8"))`` threads one policy string end to end.

Telemetry (``repro.obs``): the engine always carries a metrics
:class:`~repro.obs.Registry` — the scheduler reports queue depth and
admissions, the paged cache reports pool free/used/peak pages and
speculative truncations, and :class:`EngineStats` rides its own registry
(reset with ``engine.stats``) — export both with
:meth:`metrics_snapshot` / :meth:`prometheus`.  Passing a
:class:`~repro.obs.Tracer` additionally records every tick's phase spans
(``admit`` / ``plan`` / ``device step`` / ``host sync`` / ``commit`` on
the ``engine`` track) and each slot's request lifecycle (``submit`` /
``admit`` instants, ``prefill`` chunk spans, ``decode`` window spans
carrying ``{rid, tokens, drafts, accepted}``, ``truncate`` on rejected
tails, ``retire``) as Chrome trace events — ``tracer.export(path)`` then
loads in Perfetto as a per-slot timeline.  Whether or not a tracer is
passed, each tick runs in a ``StepTraceAnnotation("serve", step_num=<n>)``
holding the same five phases as ``jax.profiler`` program spans, and the
compiled step's device ops carry the scopes of :mod:`repro.obs.trace`
(``embed``, ``layers``, the mixer kinds, ``unembed_loss``, ``sampling``);
the paged kernel is named ``paged_attention`` inside ``attention``.  All
instrumentation reads host state and the two ``(B,)`` arrays the step
already transfers (``accept`` / ``token``): tracing adds **zero device
syncs** to ``step()`` (pinned by tests/test_obs.py) and <3% tok/s on the
bench workload (``serving_obs_overhead_pct``).

Flight recorder (``repro.obs.journal``): passing
``journal=JournalRecorder(path)`` event-sources the whole drive — config
fingerprint, fault schedule, every clock sample, ``submit``/``cancel``,
a per-tick digest (plan summary, pool/prefix counters, a rolling hash
over each slot's sampled tokens) and every result — into an append-only
JSONL file that ``replay_journal(path)`` re-drives deterministically,
naming the first divergent tick on mismatch, and that
``python -m repro.obs.postmortem`` renders as a per-request incident
report.  Recording reads the same host-side state the tracer does (zero
added device syncs — the test_obs transfer pin holds with the journal
enabled) and costs <3% tok/s (``serving_journal_overhead_pct``).

Resilience: the engine assumes an adversarial world, not a cooperative
one.  Admission is bounded (``max_queue`` -> :class:`EngineOverloaded`
backpressure), pool pressure is survived by preempting the youngest
decoding slot and recomputing it through the chunked-prefill path
(greedy output token-identical, ``serve_preemptions_total`` counts the
cost), requests carry deadlines (``submit(deadline_ms=...)``) and can be
cancelled (:meth:`ServeEngine.cancel`) — both enforced at tick
boundaries with partial output delivered — and every step's window
logits pass a nonfinite guard whose verdict rides the two arrays already
transferred (a poisoned request dies with status ``"failed"``; its
batch neighbors don't notice).  A mid-tick exception fails exactly the
plan's requests and retires their slots, so pages cannot leak and the
engine keeps serving.  All of it is scriptable for chaos testing via
:mod:`repro.serve.faults` and counted/traced via ``repro.obs``
(``serve_preemptions_total`` / ``serve_timeouts_total`` /
``serve_cancelled_total`` / ``serve_nonfinite_total`` /
``serve_failed_total``; ``preempt`` / ``timeout`` / ``cancelled`` /
``nonfinite`` / ``failed`` tracer instants).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant
from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.obs.registry import Registry, merged_prometheus, merged_snapshot
from repro.obs.trace import PhaseSpans, Tracer, scope
from repro.serve.cache import PagedKVCache
from repro.serve.faults import FaultInjector, InjectedFault
from repro.serve.metrics import EngineStats, RequestMetrics
from repro.serve.propose import NGramProposer, Proposer
from repro.serve.sampling import (SamplingParams, guard_nonfinite,
                                  make_verifier)
from repro.serve.scheduler import DECODE, PREFILL, Request, Scheduler

PyTree = Any

#: tracer track ids: tid 0 is the engine-phase track, slot b is 1 + b
TID_ENGINE = 0


def _slot_tid(slot_id: int) -> int:
    return 1 + slot_id


class EngineOverloaded(RuntimeError):
    """Typed backpressure from ``submit()`` when the bounded queue is
    full (``ServeEngine(max_queue=...)``).

    Carries ``queue_depth`` (requests waiting), ``max_queue``, and
    ``est_wait_s`` — a rough admission estimate (pending token work /
    observed throughput; None before any throughput history) — so a
    client can back off intelligently instead of retrying hot.
    """

    def __init__(self, queue_depth: int, max_queue: int,
                 est_wait_s: Optional[float] = None):
        self.queue_depth = queue_depth
        self.max_queue = max_queue
        self.est_wait_s = est_wait_s
        eta = ("no throughput history yet" if est_wait_s is None
               else f"~{est_wait_s:.2f}s of queued work ahead")
        super().__init__(
            f"engine overloaded: {queue_depth} requests waiting "
            f"(max_queue={max_queue}), {eta} — back off and resubmit")


@dataclasses.dataclass
class RequestResult:
    """A finished request: generated tokens + lifecycle metrics.

    ``status`` is the request's terminal disposition — partial output is
    always delivered alongside it, never dropped:

    - ``"ok"`` — ran to completion (``max_new`` tokens).  This includes
      requests that were preempted and recomputed along the way
      (``metrics.preemptions`` counts the evictions; greedy output is
      token-identical to an unpreempted run).
    - ``"cancelled"`` — retired by :meth:`ServeEngine.cancel` at a tick
      boundary; ``tokens`` holds whatever had been generated.
    - ``"timeout"`` — its ``deadline_ms`` passed; partial tokens.
    - ``"failed"`` — killed by the nonfinite-logit guard or a device-step
      / commit error; ``metrics.error`` says why.
    """
    request_id: int
    prompt: List[int]
    tokens: List[int]
    metrics: RequestMetrics
    status: str = "ok"


class ServeEngine:
    """Mixed-precision inference engine over the paged state pool.

    ``submit()`` enqueues requests; ``step()`` runs one scheduler tick
    (admit -> one mixed prefill+decode batch step with window
    verification -> retire finished); ``drain()`` steps until idle and
    returns results ordered by request id.  ``max_batched_tokens`` bounds
    the real tokens per step (committed decode tokens are planned first;
    draft windows and prefill chunks fill the remainder).
    ``spec_tokens`` sets the speculative window (0 disables);
    ``proposer`` overrides the default n-gram prompt-lookup drafter.

    ``prefix_cache=True`` turns on refcounted prefix-page sharing in the
    pool (pure-attention stacks; see :mod:`repro.serve.cache`): requests
    whose prompts begin with resident committed pages admit with those
    pages mapped shared into their tables and skip the cached prefix in
    prefill — ``RequestResult.metrics.cached_prefix_tokens`` counts the
    absorbed work, and ``serve_prefix_hits_total`` /
    ``serve_prefix_miss_total`` / ``serve_cow_copies_total`` /
    ``serve_pages_shared`` track the sharing layer.  Greedy output is
    token-identical with the flag on or off.

    Resilience knobs: ``max_queue`` bounds admission (``submit()`` raises
    :class:`EngineOverloaded` instead of queueing unboundedly);
    ``preempt`` enables eviction-and-recompute of the youngest decoding
    slot under pool pressure (on by default — with a default-sized pool
    it can never fire); ``submit(deadline_ms=...)`` and ``cancel(rid)``
    retire requests at tick boundaries with partial output (statuses on
    :class:`RequestResult`); every step's window logits pass a
    nonfinite guard that fails only the poisoned request.  ``faults``
    accepts a :class:`~repro.serve.faults.FaultInjector` (chaos
    testing); ``clock`` an alternative ``time.perf_counter`` (deadline
    tests use :class:`~repro.serve.faults.FakeClock` — defaults to the
    injector's clock when it has one).  ``journal`` accepts a
    :class:`~repro.obs.journal.JournalRecorder`: the flight recorder
    event-sources the drive for deterministic replay and postmortem
    analysis (see the module docstring and :mod:`repro.obs.journal`).
    """

    def __init__(self, cfg: ModelConfig, params: PyTree, *,
                 n_slots: int = 4, max_seq: int = 256,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 chunk_size: int = 32,
                 max_batched_tokens: Optional[int] = None,
                 sampling: SamplingParams = SamplingParams(),
                 spec_tokens: int = 0,
                 proposer: Optional[Proposer] = None,
                 use_kernel: bool = False, pages_per_block: int = 1,
                 kv_dtype="bf16", seed: int = 0,
                 prefix_cache: bool = False,
                 max_queue: Optional[int] = None,
                 preempt: bool = True,
                 faults: Optional[FaultInjector] = None,
                 clock: Optional[Callable[[], float]] = None,
                 registry: Optional[Registry] = None,
                 tracer: Optional[Tracer] = None,
                 journal=None):
        if not cfg.supports_decode():
            raise ValueError(
                f"{cfg.name} ({cfg.family}) does not support decode — "
                f"serving needs a causal LM stack")
        # fail fast (and with the layer kind named) before any state is
        # allocated, instead of a trace-time error from serve_forward
        tfm._require_paged_support(cfg)
        self.cfg = cfg
        self.params = params
        # engine-level telemetry is always on (host ints, zero device
        # cost); the tracer is opt-in.  EngineStats keeps a *separate*
        # registry so `engine.stats = EngineStats(n)` resets cleanly.
        self.registry = registry if registry is not None else Registry()
        self.tracer = tracer
        if tracer is not None:
            tracer.thread_name(TID_ENGINE, "engine")
            for b in range(n_slots):
                tracer.thread_name(_slot_tid(b), f"slot {b}")
        if hasattr(kv_dtype, "kv_dtype"):     # a core.policy.Policy
            kv_dtype = kv_dtype.kv_dtype
        # a format the device cannot serve fails here, naming both —
        # never a silent fallback to another storage dtype
        self.kv_format = quant.require_supported(kv_dtype, jax.devices()[0])
        self.spec_tokens = int(spec_tokens)
        if proposer is not None and self.spec_tokens == 0:
            raise ValueError(
                "a proposer without spec_tokens > 0 would never be "
                "consulted — pass spec_tokens=k to size the speculative "
                "window")
        recurrent = sorted(set(cfg.layer_kinds()) & {"rglru", "ssd"})
        if self.spec_tokens > 0 and recurrent:
            raise ValueError(
                f"spec_tokens={self.spec_tokens}: speculative windows "
                f"need the state layer to roll back rejected draft "
                f"positions, and {cfg.name}'s "
                f"{', '.join(repr(k) for k in recurrent)} layer(s) carry "
                f"O(1) recurrent slot state that only moves forward — "
                f"there is no written-watermark to truncate back to the "
                f"way KV pages have.  Serve this model with "
                f"spec_tokens=0 (snapshot-and-restore of recurrent state "
                f"on rejection is the named follow-on).")
        if self.spec_tokens > 0 and proposer is None:
            proposer = NGramProposer()
        self.proposer = proposer
        self.cache = PagedKVCache(cfg, n_slots, max_seq,
                                  page_size=page_size, num_pages=num_pages,
                                  kv_dtype=self.kv_format,
                                  prefix_cache=prefix_cache,
                                  registry=self.registry)
        self.scheduler = Scheduler(self.cache, chunk_size=chunk_size,
                                   max_batched_tokens=max_batched_tokens,
                                   spec_tokens=self.spec_tokens,
                                   proposer=self.proposer,
                                   preempt=preempt,
                                   registry=self.registry)
        self.sampling = sampling
        self.stats = EngineStats(n_slots)
        self._key = jax.random.key(seed)
        self._next_id = 0
        self._inflight: dict[int, RequestMetrics] = {}
        self._results: List[RequestResult] = []
        self._result_ids: set[int] = set()   # finished, kept for drain()
        # drain()'s no-progress guard reads these per-tick flags
        self._last_tick_admitted = False
        self._last_tick_stepped = False
        self._ticks = 0
        # resilience state: bounded admission, deadlines/cancellation at
        # tick boundaries, fault injection, injectable clock
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1: {max_queue}")
        self.max_queue = max_queue
        self.faults = faults
        if clock is None and faults is not None and faults.clock is not None:
            clock = faults.clock
        self._clock: Callable[[], float] = (clock if clock is not None
                                            else time.perf_counter)
        # flight recorder (repro.obs.journal — duck-typed so the replay
        # hook plugs in the same seam): wrap the clock FIRST so every
        # sample the engine ever draws is journaled, then hand over the
        # config fingerprint + fault schedule for the header
        self.journal = journal
        if journal is not None:
            self._clock = journal.wrap_clock(self._clock)
            journal.on_attach(
                {"config": dataclasses.asdict(cfg),
                 "engine": {
                     "n_slots": n_slots, "max_seq": max_seq,
                     "page_size": page_size,
                     "num_pages": self.cache.num_pages,
                     "chunk_size": chunk_size,
                     "max_batched_tokens": max_batched_tokens,
                     "sampling": dataclasses.asdict(sampling),
                     "spec_tokens": self.spec_tokens,
                     "proposer": (None if self.proposer is None
                                  else type(self.proposer).__name__),
                     "use_kernel": bool(use_kernel),
                     "pages_per_block": pages_per_block,
                     "kv_dtype": self.kv_format.name,
                     "seed": seed,
                     "prefix_cache": self.cache.prefix_cache,
                     "max_queue": max_queue,
                     "preempt": bool(preempt)}},
                faults)
        self._deadlines: dict[int, float] = {}   # rid -> absolute expiry
        self._cancelled: set[int] = set()        # applied at tick start
        # the always-present poison operand for the jitted step (host
        # numpy, built once — jnp.asarray per step is a host->device
        # transfer, not a sync; the test_obs transfer pin counts only
        # device->host np.asarray calls)
        self._zero_poison = np.zeros(n_slots, np.bool_)
        self._timeouts = self.registry.counter(
            "serve_timeouts_total", "requests retired at their deadline")
        self._cancels = self.registry.counter(
            "serve_cancelled_total", "requests cancelled by the client")
        self._nonfinite = self.registry.counter(
            "serve_nonfinite_total",
            "requests failed by the nonfinite-logit guard")
        self._failures = self.registry.counter(
            "serve_failed_total",
            "requests failed by a device-step or commit error "
            "(includes nonfinite-guard kills)")

        verifier = make_verifier(sampling)

        def raw_step(params, pages, table, tokens, start, valid,
                     logit_idx, draft, draft_len, poison, key):
            # serve_forward returns the (B, W, V) window logits named by
            # logit_idx — the unembed runs once per window position, not
            # per chunk position; verification/sampling runs in fp32
            logits, new_pages = tfm.serve_forward(
                params, cfg, pages, table, tokens, start, valid,
                logit_idx=logit_idx, page_size=page_size,
                use_kernel=use_kernel, pages_per_block=pages_per_block,
                kv_format=self.kv_format.name)
            # fault seam: NaN-poison the masked slots' windows *before*
            # verification, so injected poison exercises the exact guard
            # path a real quantized-overflow NaN would take
            logits = jnp.where(poison[:, None, None], jnp.nan, logits)
            with scope("sampling"):
                accept, token = verifier(logits, draft, draft_len, key)
                # nonfinite-logit guard: verdict rides the two (B,) arrays
                # already transferred (token -1 = failure sentinel) — zero
                # added syncs
                accept, token = guard_nonfinite(logits, accept, token)
            return accept, token, new_pages

        # one compiled step shape AND program: (B, chunk_size) for
        # prefill, decode and mixed plans alike — the paged kernel covers
        # every plan, and the W-wide verify covers spec_tokens = 0 (W=1,
        # zero drafts) through full windows with no extra specialization.
        self._device_step = jax.jit(raw_step, donate_argnums=(1,))

    # -- public API ---------------------------------------------------------

    def submit(self, prompt: List[int], max_new: int = 32,
               request_id: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Enqueue a request; returns its id.

        An explicit ``request_id`` colliding with a queued, in-flight, or
        already-finished request is rejected — a duplicate would corrupt
        that request's metrics entry and collide in ``drain()``'s
        id-sorted results (results accumulate for the engine's lifetime).

        ``deadline_ms`` caps end-to-end latency: at the first tick
        boundary at or past the deadline the request is retired with
        status ``"timeout"`` and whatever tokens it has.  With
        ``max_queue`` configured, a full waiting queue raises
        :class:`EngineOverloaded` (typed backpressure carrying queue
        depth and an admission estimate) before any state is touched.
        """
        # fail fast on a stub proposer: plan() would otherwise raise mid-
        # step, after this request reserved pages and entered a batch —
        # a traceback from inside the scheduler instead of an actionable
        # "this is a follow-on" at the API boundary
        unimplemented = getattr(self.proposer, "unimplemented", None)
        if unimplemented:
            raise NotImplementedError(unimplemented)
        if (self.max_queue is not None
                and len(self.scheduler.waiting) >= self.max_queue):
            raise EngineOverloaded(len(self.scheduler.waiting),
                                   self.max_queue,
                                   self._admission_estimate())
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0: {deadline_ms}")
        rid = self._next_id if request_id is None else request_id
        if rid in self._inflight or rid in self._result_ids:
            raise ValueError(
                f"request id {rid} is already queued, in flight, or "
                f"finished — engine request ids are single-use")
        self.scheduler.submit(Request(rid, list(prompt), max_new))
        self._next_id = max(self._next_id, rid) + 1
        now = self._clock()
        self._inflight[rid] = RequestMetrics(
            request_id=rid, prompt_len=len(prompt), submit_time=now)
        if deadline_ms is not None:
            self._deadlines[rid] = now + deadline_ms / 1e3
        if self.journal is not None:
            self.journal.record_submit(rid, prompt, max_new, deadline_ms)
        if self.tracer is not None:
            self.tracer.instant("submit", tid=TID_ENGINE, rid=rid,
                                prompt_len=len(prompt), max_new=max_new)
        return rid

    def cancel(self, rid: int) -> bool:
        """Request cancellation of a queued or in-flight request.

        Enforced at the next tick boundary: the request is retired with
        status ``"cancelled"`` and its partial output delivered.  Returns
        False for ids that are unknown or already finished (cancellation
        raced completion — the existing result stands).
        """
        if rid not in self._inflight:
            return False
        self._cancelled.add(rid)
        if self.journal is not None:
            self.journal.record_cancel(rid)
        return True

    def _admission_estimate(self) -> Optional[float]:
        """Rough seconds of queued+running token work ahead of a new
        request, from observed throughput (None without history)."""
        if self.stats.elapsed <= 0 or self.stats.total_new_tokens == 0:
            return None
        pending = sum(s.req.max_new - len(s.out)
                      for s in self.scheduler.slots if s is not None)
        pending += sum(r.max_new - len(r.resume_out or [])
                       for r in self.scheduler.waiting)
        return pending / self.stats.throughput_tok_per_s

    def step(self) -> List[RequestResult]:
        """One scheduler tick.  Returns requests that finished this step
        — by completion or by any resilience path (cancellation, deadline
        expiry, the nonfinite guard, a device failure: see
        :class:`RequestResult.status`).  Cancel/deadline sweeps run at
        the tick boundary, before admission, so an expired slot's pages
        are reclaimed in time for this tick's admissions.

        ``EngineStats.elapsed`` covers the **full** tick — admission
        through commit — so host-side scheduler work is charged to the
        step it belongs to and ``tok_per_s`` cannot flatter the engine by
        excluding it (regression-tested against ``drain()`` wall time).
        """
        self._ticks += 1
        with jax.profiler.StepTraceAnnotation("serve", step_num=self._ticks), \
                PhaseSpans() as phase:
            return self._tick(phase)

    def _tick(self, phase: PhaseSpans) -> List[RequestResult]:
        phase.start("admit")
        tr = self.tracer
        t0 = self._clock()
        tick_us = tr.now_us() if tr is not None else 0.0
        if self.faults is not None:
            self.faults.begin_tick(self.cache)
        results: List[RequestResult] = []
        self._sweep_cancelled(results)
        self._sweep_deadlines(results)
        admitted, preempted = self.scheduler.admit()
        self._last_tick_admitted = bool(admitted)
        if self.cache.prefix_cache and admitted:
            # a slot admitted mid-feed got its prefix from shared pages:
            # the skip (slot.fed at admission, before any plan) is the
            # request's prefill work the cache absorbed
            admitted_set = set(admitted)
            for slot in self.scheduler.slots:
                if (slot is not None
                        and slot.req.request_id in admitted_set
                        and slot.fed > 0):
                    self._inflight[slot.req.request_id] \
                        .cached_prefix_tokens += slot.fed
        for rid in preempted:
            rm = self._inflight[rid]
            rm.preemptions += 1
            rm.last_evict_time = t0
            if tr is not None:
                tr.instant("preempt", tid=TID_ENGINE, rid=rid)
        # phase bookkeeping: first admission ends queue wait; a
        # re-admission after preemption closes the preempted-recompute gap
        # (processed after the preempted loop so a same-tick
        # evict-and-readmit charges zero preempted time)
        for rid in admitted:
            rm = self._inflight[rid]
            if rm.admit_time is None:
                rm.admit_time = t0
            if rm.last_evict_time is not None:
                rm.preempted_seconds += t0 - rm.last_evict_time
                rm.last_evict_time = None
        if tr is not None:
            t_admit = tr.now_us()
            tr.complete("admit", tick_us, t_admit - tick_us,
                        tid=TID_ENGINE,
                        args={"admitted": list(admitted),
                              "preempted": list(preempted)})
            for rid in admitted:
                tr.instant("admit", tid=TID_ENGINE, rid=rid)
        if self.scheduler.busy_slots == 0:
            self._last_tick_stepped = False
            self._journal_tick("idle", admitted, preempted, results)
            return results
        self._last_tick_stepped = True
        phase.start("plan")
        if tr is not None:
            plan_us = tr.now_us()
        plan = self.scheduler.plan()
        if self.sampling.is_greedy:
            key = self._key
        else:
            self._key, key = jax.random.split(self._key)
        slot_rids = [None if s is None else s.req.request_id
                     for s in self.scheduler.slots]
        # pre-commit slot snapshot: if commit() raises partway, the
        # cleanup path still knows each request's partial output
        slot_objs = list(self.scheduler.slots)
        poison = (self.faults.poison_mask(slot_rids)
                  if self.faults is not None else self._zero_poison)
        if tr is not None:
            dev_us = tr.now_us()
            tr.complete("plan", plan_us, dev_us - plan_us, tid=TID_ENGINE,
                        args=plan.summary())
        phase.start("device step")
        try:
            if self.faults is not None:
                # raised before the device call, while the donated page
                # buffers are still intact
                self.faults.maybe_fail_step()
            # dispatch pending copy-on-write page copies (queued by
            # admission / note_write) before the step can write into the
            # copies' target pages — async, no host sync
            self.cache.flush_cow()
            accept, token, self.cache.pages = self._device_step(
                self.params, self.cache.pages, self.cache.table_device(),
                jnp.asarray(plan.tokens), jnp.asarray(plan.start),
                jnp.asarray(plan.valid), jnp.asarray(plan.logit_idx),
                jnp.asarray(plan.draft), jnp.asarray(plan.draft_len),
                jnp.asarray(poison), key)
            if tr is not None:
                sync_us = tr.now_us()
                tr.complete("device step", dev_us, sync_us - dev_us,
                            tid=TID_ENGINE, args={"kind": plan.kind})
            phase.start("host sync")
            accept = np.asarray(accept)               # blocks on the device
            token = np.asarray(token)
            now = self._clock()
            phase.start("commit")
            if tr is not None:
                commit_us = tr.now_us()
                tr.complete("host sync", sync_us, commit_us - sync_us,
                            tid=TID_ENGINE)

            # per-request speculation accounting, against the pre-commit
            # slot->request mapping (commit retires finished slots)
            for slot_id, rid in enumerate(slot_rids):
                k = int(plan.draft_len[slot_id])
                if rid is None or k == 0:
                    continue
                rm = self._inflight[rid]
                rm.proposed_tokens += k
                rm.accepted_tokens += int(accept[slot_id])

            # nonfinite-guard verdicts: token -1 flags a slot whose
            # window logits held NaN/Inf.  Fail just that request —
            # slot retired, pages reclaimed, partial output delivered —
            # and zero its plan entry so commit() skips it; the rest of
            # the batch continues untouched.
            for slot_id, rid in enumerate(slot_rids):
                if (rid is None or plan.valid[slot_id] == 0
                        or token[slot_id] >= 0):
                    continue
                self._nonfinite.inc()
                if tr is not None:
                    tr.instant("nonfinite", tid=_slot_tid(slot_id),
                               rid=rid)
                slot = self.scheduler.evict(slot_id)
                results.append(self._finish_request(
                    rid, slot.req.prompt, list(slot.out), "failed", now,
                    error="nonfinite logits in decode window"))
                plan.valid[slot_id] = 0

            outcome = self.scheduler.commit(plan, token, accept)
        except Exception as err:
            # commit/retire discipline under mid-tick failure: every
            # request the plan touched is failed + retired, so an
            # exception here can never leak pages or leave a slot busy
            results.extend(self._fail_plan(plan, slot_rids, slot_objs,
                                           err, self._clock()))
            if isinstance(err, InjectedFault):
                # scripted fault: keep serving.  The tick is journaled
                # (deterministic — the schedule is in the header); a real
                # exception re-raises WITHOUT a tick record, so replay
                # knows the final results belong to an aborted tick.
                self._journal_tick("fault", admitted, preempted, results,
                                   plan=plan)
                return results
            raise
        first = set(outcome.first_token)
        for rid, _ in outcome.emitted:
            rm = self._inflight[rid]
            if rid in first:
                rm.first_token_time = now
            else:
                # one gap per request per step: a speculative window's
                # tokens arrive together, so the gap spans the whole batch
                self.stats.record_token_gap(now - rm.last_token_time)
            rm.last_token_time = now
        for _, slot in outcome.finished:
            results.append(self._finish_request(
                slot.req.request_id, slot.req.prompt, slot.out, "ok", now))
        if tr is not None:
            end_us = tr.now_us()
            tr.complete("commit", commit_us, end_us - commit_us,
                        tid=TID_ENGINE,
                        args={"emitted": outcome.n_tokens,
                              "finished": len(outcome.finished)})
            tr.complete("tick", tick_us, end_us - tick_us, tid=TID_ENGINE,
                        args={"kind": plan.kind})
            self._trace_slots(plan, slot_rids, accept, outcome,
                              dev_us, sync_us)
        t_end = self._clock()
        self.stats.record_step(
            plan.kind, self.scheduler.busy_slots + len(outcome.finished),
            outcome.n_tokens, t_end - t0,
            prefill_tokens=np.where(plan.kinds == PREFILL, plan.valid, 0),
            decode_tokens=np.where(plan.kinds == DECODE, plan.valid, 0),
            proposed=plan.n_draft,
            accepted=int(accept.sum()))
        self._journal_tick(plan.kind, admitted, preempted, results,
                           plan=plan, slot_rids=slot_rids, accept=accept,
                           token=token, outcome=outcome)
        return results

    # -- resilience internals -----------------------------------------------

    def _journal_tick(self, kind: str, admitted, preempted, results,
                      plan=None, slot_rids=None, accept=None, token=None,
                      outcome=None) -> None:
        """Feed the flight recorder one tick's digest.

        Reads only host-side state: the plan summary, the scheduler's
        admit/preempt lists, pool/prefix counters (host ints on the
        cache), and the two already-transferred ``(B,)`` arrays — like
        the tracer, zero added device syncs (the test_obs transfer pin
        runs with the journal enabled).
        """
        if self.journal is None:
            return
        c = self.cache
        digest = {"kind": kind,
                  "admitted": list(admitted), "preempted": list(preempted),
                  "tokens": plan.n_tokens if plan is not None else 0,
                  "drafts": plan.n_draft if plan is not None else 0,
                  "accepted": int(accept.sum()) if accept is not None else 0,
                  "emitted": outcome.n_tokens if outcome is not None else 0,
                  "finished": [[r.request_id, r.status] for r in results],
                  "pool": [c.free_pages, c.used_pages, c.cached_pages,
                           c.shared_pages, c.held_pages],
                  "prefix": [c.prefix_hits, c.prefix_misses, c.cow_copies]}
        tok_items = []
        if token is not None:
            for slot_id, rid in enumerate(slot_rids):
                if rid is None or plan.valid[slot_id] == 0:
                    continue
                tok_items.append((slot_id, rid, int(token[slot_id]),
                                  int(accept[slot_id])))
        self.journal.record_tick(digest, tok_items)

    def _finish_request(self, rid: int, prompt: List[int],
                        tokens: List[int], status: str, now: float,
                        error: Optional[str] = None) -> RequestResult:
        """The single exit point for every terminal status: retire the
        request's engine-side bookkeeping and deliver its result (partial
        output included — never dropped)."""
        rm = self._inflight.pop(rid)
        self._result_ids.add(rid)
        self._deadlines.pop(rid, None)
        self._cancelled.discard(rid)
        rm.finish_time = now
        rm.new_tokens = len(tokens)
        if error is not None:
            rm.error = error
        self.stats.record_finish(rm)
        counter = {"cancelled": self._cancels, "timeout": self._timeouts,
                   "failed": self._failures}.get(status)
        if counter is not None:
            counter.inc()
        if self.tracer is not None and status != "ok":
            self.tracer.instant(status, tid=TID_ENGINE, rid=rid)
        result = RequestResult(rid, list(prompt), list(tokens), rm, status)
        self._results.append(result)
        if self.journal is not None:
            self.journal.record_result(result)
        return result

    def _terminate(self, rid: int, status: str, now: float,
                   error: Optional[str] = None) -> RequestResult:
        """Retire a queued or in-flight request before completion —
        reclaiming its slot and pages — with a terminal status."""
        req = self.scheduler.remove_waiting(rid)
        if req is not None:
            # still queued; a preempted requeue carries partial output
            return self._finish_request(rid, req.prompt,
                                        list(req.resume_out or []),
                                        status, now, error=error)
        for slot_id, slot in enumerate(self.scheduler.slots):
            if slot is not None and slot.req.request_id == rid:
                self.scheduler.evict(slot_id)
                return self._finish_request(rid, slot.req.prompt,
                                            list(slot.out), status, now,
                                            error=error)
        raise RuntimeError(
            f"request {rid} is tracked as in flight but sits in no "
            f"queue or slot — engine/scheduler bookkeeping diverged")

    def _sweep_cancelled(self, results: List[RequestResult]) -> None:
        """Apply pending cancel() calls at the tick boundary."""
        if not self._cancelled:
            return
        now = self._clock()
        for rid in sorted(self._cancelled):
            if rid in self._inflight:
                results.append(self._terminate(rid, "cancelled", now))
        self._cancelled.clear()

    def _sweep_deadlines(self, results: List[RequestResult]) -> None:
        """Retire every request whose deadline has passed (status
        "timeout", partial output delivered)."""
        if not self._deadlines:
            return
        now = self._clock()
        expired = [rid for rid, t in self._deadlines.items()
                   if now >= t and rid in self._inflight]
        for rid in expired:
            results.append(self._terminate(
                rid, "timeout", now,
                error=f"deadline exceeded at t={now:.3f}"))

    def _fail_plan(self, plan, slot_rids, slot_objs, err: Exception,
                   now: float) -> List[RequestResult]:
        """Cleanup after an exception between the device step and the end
        of commit: every request the plan touched is failed and its slot
        retired.  Requests commit() finished before raising lost their
        outcome with the exception, so they are failed too, with the
        partial output the pre-commit snapshot recorded."""
        failed = []
        for slot_id, rid in enumerate(slot_rids):
            if rid is None or plan.valid[slot_id] == 0:
                continue
            if rid not in self._inflight:
                continue               # finished before the exception
            slot = self.scheduler.slots[slot_id]
            if slot is not None and slot.req.request_id == rid:
                self.scheduler.evict(slot_id)
                tokens = list(slot.out)
            else:
                # commit retired the slot before raising — fall back to
                # the snapshot's view of the partial output
                tokens = list(slot_objs[slot_id].out)
            failed.append(self._finish_request(
                rid, slot_objs[slot_id].req.prompt, tokens, "failed",
                now, error=f"{type(err).__name__}: {err}"))
        return failed

    def _trace_slots(self, plan, slot_rids, accept, outcome,
                     dev_us: float, sync_us: float) -> None:
        """Per-slot lifecycle events for one tick.

        Each live slot gets an "X" span over the device-step interval on
        its own track (Perfetto renders a per-slot timeline); decode
        spans carry the window's draft/accept counts, rejected tails get
        a ``truncate`` instant, retiring slots a ``retire`` instant.
        Reads only the host-side plan and the already-transferred
        ``accept`` array — no device access.
        """
        tr = self.tracer
        dur = sync_us - dev_us
        finished = {slot_id for slot_id, _ in outcome.finished}
        for slot_id, rid in enumerate(slot_rids):
            if rid is None or plan.valid[slot_id] == 0:
                continue
            tid = _slot_tid(slot_id)
            if plan.kinds[slot_id] == PREFILL:
                tr.complete("prefill", dev_us, dur, tid=tid,
                            args={"rid": rid,
                                  "tokens": int(plan.valid[slot_id]),
                                  "start": int(plan.start[slot_id])})
            else:
                k = int(plan.draft_len[slot_id])
                acc = int(accept[slot_id])
                tr.complete("decode", dev_us, dur, tid=tid,
                            args={"rid": rid,
                                  "tokens": int(plan.valid[slot_id]),
                                  "drafts": k, "accepted": acc})
                if k > acc:
                    tr.instant("truncate", tid=tid,
                               rid=rid, rejected=k - acc)
            if slot_id in finished:
                tr.instant("retire", tid=tid, rid=rid)

    def drain(self) -> List[RequestResult]:
        """Run until queue and slots are empty; all results, by id.

        Guards against the no-progress spin: if a full tick admits
        nothing, runs no device step, and retires nothing while requests
        are still waiting, no future tick can differ (admission is the
        only way forward and its inputs didn't change) — raise an
        actionable error naming the stuck requests instead of looping
        forever.  Two resilience carve-outs: a request stuck only because
        its deadline expired is *swept* (status "timeout") rather than
        spun on — the sweep counts as progress and drain terminates —
        and a fault injector with events still scheduled counts as
        progress too (a scripted exhaustion window lifts at its
        scheduled tick).
        """
        while self.scheduler.has_work:
            n_results = len(self._results)
            self.step()
            progressed = (self._last_tick_admitted
                          or self._last_tick_stepped
                          or len(self._results) > n_results
                          or (self.faults is not None
                              and self.faults.pending))
            if not progressed:
                stuck = [r.request_id for r in self.scheduler.waiting]
                held = self.cache.held_pages
                hint = (f"  ({held} pages are held by fault injection "
                        f"with no scheduled release.)" if held else "")
                raise RuntimeError(
                    f"ServeEngine.drain(): no progress — tick admitted "
                    f"nothing, stepped nothing, and retired nothing, but "
                    f"requests {stuck} are still waiting.  The head "
                    f"request cannot fit the page pool "
                    f"({self.cache.free_pages} of {self.cache.num_pages} "
                    f"pages free, {self.cache.max_pages_per_slot} max per "
                    f"slot); submit() should have rejected it — if it "
                    f"was enqueued by other means, resize the pool or "
                    f"split the request.{hint}")
        return sorted(self._results, key=lambda r: r.request_id)

    # -- telemetry exports --------------------------------------------------

    def metrics_snapshot(self) -> dict:
        """Flat dict over the engine registry (queue/pool/admissions)
        and the stats registry (steps/tokens/latency histograms)."""
        return merged_snapshot(self.registry, self.stats.registry)

    def prometheus(self) -> str:
        """Prometheus text exposition of both registries (the
        ``--metrics-out`` artifact)."""
        return merged_prometheus(self.registry, self.stats.registry)
