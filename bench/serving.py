"""What the two serving drivers share: the engine, the tick loop's
bookkeeping, the traced segment and the check against the reference.

The engine is the program's ``ServeEngine`` with the mix's settings and
``use_kernel=True``, over bfloat16 weights the benchmark makes on the
device.  Every token is timed on the host clock when the ``step()``
that produced it returns (``step()`` ends in a blocking read of the
sampled tokens); admission is seen as the first tick after which a
request sits in a slot.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, trace_reduce, weights


def pct(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least a share
    ``q`` of the values at or below it (``inf`` counts as slowest)."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


@dataclasses.dataclass
class Req:
    prompt: list
    max_new: int
    due: float = math.nan           # host clock (s) the request was due
    admitted: float = math.nan      # tick start at which it held a slot
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = None             # served tokens, once finished
    status: str = None
    finished: float = math.nan


class Book:
    """Requests in flight and every token's host time, tick by tick."""

    def __init__(self, engine):
        self.engine = engine
        self.reqs: dict = {}
        self.ticks = 0
        self.plans = None           # (start, valid) per tick when recording

    def submit(self, rid: int, req: Req) -> None:
        self.reqs[rid] = req
        self.engine.submit(req.prompt, max_new=req.max_new, request_id=rid)

    def _served(self) -> dict:
        return {s.req.request_id: len(s.out)
                for s in self.engine.scheduler.slots if s is not None}

    def tick(self) -> list:
        """One ``engine.step()``; returns the requests that finished."""
        before = self._served()
        t0 = time.perf_counter()
        results = self.engine.step()
        t1 = time.perf_counter()
        self.ticks += 1
        after = self._served()
        for rid in after.keys() - before.keys():
            self.reqs[rid].admitted = t0
        done = []
        for r in results:
            req = self.reqs[r.request_id]
            if math.isnan(req.admitted):
                req.admitted = t0
            after[r.request_id] = len(r.tokens)
            req.tokens, req.status, req.finished = list(r.tokens), r.status, t1
            done.append(r.request_id)
        for rid, n in after.items():
            gained = n - before.get(rid, 0)
            self.reqs[rid].token_times.extend([t1] * gained)
        return done

    def record_plans(self) -> None:
        """Keep each tick's per-slot (start, valid), read from the plan
        the scheduler returns (the tracer's plan span does not carry
        them yet)."""
        sched = self.engine.scheduler
        plan = type(sched).plan.__get__(sched)
        self.plans = []

        def recording():
            p = plan()
            self.plans.append((p.start.copy(), p.valid.copy()))
            return p

        sched.plan = recording

    def stop_recording(self) -> None:
        del self.engine.scheduler.plan


def make_engine(run: harness.Run, tracer=None):
    """The config's model with benchmark-made bf16 weights, and the
    engine the mix states."""
    from repro import serve
    from repro.models import transformer
    cfg = harness.program_config(run.cell.config)
    params = weights.make(transformer.param_shapes(cfg), run.seed,
                          jnp.bfloat16)
    jax.block_until_ready(params)
    run.log("weights made on the device")
    engine = serve.ServeEngine(cfg, params, use_kernel=True, tracer=tracer,
                               **run.cell.traffic["engine"])
    if "token_altered" in run.faults:
        _alter_tokens(engine)
    if "state_unchanged" in run.faults:
        _keep_state(engine)
    return cfg, params, engine


def _alter_tokens(engine) -> None:
    """Fault: every 4th tick's sampled tokens come out one id higher."""
    step, count = engine._device_step, [0]
    vocab = engine.cfg.vocab_size

    def altered(*args):
        accept, token, pages = step(*args)
        count[0] += 1
        if count[0] % 4 == 0:
            token = jnp.where(token >= 0, (token + 1) % vocab, token)
        return accept, token, pages

    engine._device_step = altered


def _keep_state(engine) -> None:
    """Fault: the step hands back the cache it was given, unwritten."""
    step = jax.jit(engine._device_step.__wrapped__)

    def unchanged(params, pages, *args):
        keep = jax.tree.map(jnp.copy, pages)
        accept, token, _ = step(params, pages, *args)
        return accept, token, keep

    engine._device_step = unchanged


def warm(engine, prompt_len: int, log) -> None:
    """Compile the engine's one step program on a short request."""
    t = time.perf_counter()
    engine.submit(list(range(1, prompt_len + 1)), max_new=2,
                  request_id=-1)
    engine.drain()
    log(f"engine step compiled or loaded in {time.perf_counter() - t:.3f}s")


class Traced:
    """Drives the window's ticks; in a traced run, a profiler trace
    covers the whole ticks that start in ``[at, at + seconds)``.  The
    trace is read once the run's ticks are over (``layer()``), so that
    reading it holds up no request."""

    def __init__(self, run: harness.Run, book: Book, tracer, at: float,
                 seconds: float):
        self.run, self.book, self.tracer = run, book, tracer
        self.at, self.seconds = at, seconds
        self.tick_starts = []
        self.ann = None
        self.stopped = False

    def tick(self) -> list:
        if self.tracer is None or self.stopped:
            return self.book.tick()
        now = time.perf_counter()
        if self.ann is None:
            if now < self.at:
                return self.book.tick()
            self._start()
        self.tick_starts.append(now)
        with jax.profiler.TraceAnnotation("engine tick"):
            done = self.book.tick()
        if time.perf_counter() - self.tick_starts[0] >= self.seconds:
            self._stop()
        return done

    @property
    def tracing(self) -> bool:
        """A trace has started and not yet stopped."""
        return self.ann is not None and not self.stopped

    def _start(self) -> None:
        harness.clear_traces(self.run.trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.run.trace_dir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self.ann.__enter__()
        self.book.record_plans()

    def _stop(self) -> None:
        t = time.perf_counter()
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.book.stop_recording()
        self.stopped = True
        self.run.log(f"trace of {len(self.tick_starts)} ticks stopped in "
                     f"{time.perf_counter() - t:.3f}s")

    def layer(self) -> dict:
        """The traced context the readers take, or ``{}`` untraced."""
        if not self.stopped:
            return {}
        trace = trace_reduce.load(self.run.trace_dir)
        harness.clear_traces(self.run.trace_dir)
        self._add_engine_spans(trace)
        return {"trace": trace, "plans": self.book.plans,
                "host_ms": self._host_ms(trace),
                "model": self.run.cell.config["model"],
                "costs": self.run.cell.costs,
                "engine": self.run.cell.traffic["engine"],
                "peaks": self.run.peaks}

    def _offset_ns(self, trace) -> float:
        """Profiler time minus host perf_counter time (ns), from the
        ``engine tick`` annotations whose host start times were kept."""
        starts = sorted(s for name, s, _, _ in trace.host
                        if name == "engine tick")
        n = min(len(starts), len(self.tick_starts))
        return float(np.median([starts[i] - self.tick_starts[i] * 1e9
                                for i in range(n)]))

    def _add_engine_spans(self, trace) -> None:
        """The engine tracer's phase spans, moved onto profiler time, as
        host spans nested under the tick annotation."""
        t_pc = time.perf_counter()
        tracer_t0 = t_pc - self.tracer.now_us() * 1e-6
        off = self._offset_ns(trace)
        for ev in self.tracer.events:
            if ev.get("ph") != "X" or ev.get("tid") != 0:
                continue
            s = (tracer_t0 + ev["ts"] * 1e-6) * 1e9 + off
            e = s + ev["dur"] * 1e3
            if e > trace.window[0] and s < trace.window[1]:
                depth = 1 if ev["name"] == "tick" else 2
                trace.host.append(("engine: " + ev["name"], int(s), int(e),
                                   10 + depth))

    def _host_ms(self, trace) -> list:
        """Per tick: its wall time minus its ``host sync`` span (ms)."""
        spans = [(n, s, e) for n, s, e, _ in trace.host
                 if n in ("engine: tick", "engine: host sync")]
        ticks = [(s, e) for n, s, e in spans if n == "engine: tick"]
        syncs = [(s, e) for n, s, e in spans if n == "engine: host sync"]
        out = []
        for s, e in ticks:
            sync = sum(se - ss for ss, se in syncs if ss >= s and se <= e)
            out.append((e - s - sync) * 1e-6)
        return out


def open_loop(book: Book, reqs: list, due, t0: float, done, tick) -> None:
    """Submit request ``i`` of ``reqs`` at the first tick boundary at or
    after ``t0 + due[i]``, whatever the engine is doing, and ``tick()``
    while the engine has work, until ``done(now)``."""
    nxt = 0
    while True:
        now = time.perf_counter()
        while nxt < len(reqs) and t0 + due[nxt] <= now:
            prompt, new = reqs[nxt]
            book.submit(nxt, Req(prompt, new, due=t0 + due[nxt]))
            nxt += 1
        if nxt >= len(reqs):
            raise RuntimeError(f"the mix's {len(reqs)} requests ran out")
        if done(now):
            return
        if book.engine.scheduler.has_work:
            tick()
        else:
            time.sleep(max(0.0, min(t0 + due[nxt] - now, 0.005)))


def sample(book: Book, rng: np.random.Generator, since: float,
           min_tokens: int, max_requests: int) -> list:
    """Finished requests to check: the longest one, then others drawn
    from the seed, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [rid for rid, r in book.reqs.items()
            if r.status == "ok" and r.finished >= since]
    if not done:
        return []
    done.sort()
    longest = max(done, key=lambda rid: len(book.reqs[rid].prompt)
                  + len(book.reqs[rid].tokens))
    rest = [rid for rid in done if rid != longest]
    order = [longest] + [rest[i] for i in rng.permutation(len(rest))]
    picked, served = [], 0
    for rid in order:
        if served >= min_tokens or len(picked) >= max_requests:
            break
        picked.append(rid)
        served += len(book.reqs[rid].tokens)
    return picked


def logit_gap(run: harness.Run, params, book: Book, picked: list,
              fp8: bool = False) -> tuple:
    """Widest gap by which a served token's reference logit lies below
    the reference's best at that position.  With ``fp8`` the tokens are
    the float8 control's own first choices at the same positions."""
    ref = harness.reference_module(run.cell.config)
    seqs, pos = [], []
    for rid in picked:
        r = book.reqs[rid]
        seqs.append(r.prompt + r.tokens[:-1])
        pos.append(list(range(len(r.prompt) - 1,
                              len(r.prompt) - 1 + len(r.tokens))))
    t = time.perf_counter()
    logits = ref.logits_at(params, run.cell.config, seqs, pos)
    ctrl = (ref.logits_at(params, run.cell.config, seqs, pos, fp8=True)
            if fp8 else None)
    gaps, ctrl_gaps = [], []
    for i, rid in enumerate(picked):
        lg = logits[i]
        served = np.asarray(book.reqs[rid].tokens)
        best = lg.max(-1)
        gaps.append(best - lg[np.arange(len(served)), served])
        if ctrl is not None:
            pick = ctrl[i].argmax(-1)
            ctrl_gaps.append(best - lg[np.arange(len(served)), pick])
    n = sum(len(g) for g in gaps)
    run.log(f"reference over {len(picked)} requests, {n} served tokens, in "
            f"{time.perf_counter() - t:.3f}s")
    gap = float(max(g.max() for g in gaps))
    cgap = float(max(g.max() for g in ctrl_gaps)) if ctrl_gaps else None
    return gap, cgap, n
