"""Fault-tolerant trainer: checkpoint/restart, SIGTERM, step watchdog.

The production loop every launcher entry point drives:

- builds (or **resumes**) sharded train state on the given mesh,
- jits the MPX train step with explicit in/out shardings + donation,
- checkpoints every N steps (async) including **data-iterator state** and
  the loss-scaling state — a resumed run replays the identical batch and
  scaling schedule (tested bit-exact),
- installs a SIGTERM/SIGINT handler: on preemption the current state is
  checkpointed synchronously before exit (standard TPU-fleet etiquette),
- runs a **step watchdog**: a step exceeding ``watchdog_s`` marks the run
  unhealthy and raises after checkpointing — in a fleet, the scheduler
  relaunches and the run resumes from the last checkpoint; on restart with
  a different device count, elastic restore re-shards (see Checkpointer).
  This is the restart-based straggler/failure mitigation appropriate to
  synchronous SPMD (DESIGN.md §5),
- records **precision telemetry** (``repro.obs``): every logged step
  feeds the loss-scale trajectory, overflow/skip counters and
  halving/doubling events into ``trainer.precision``
  (:class:`~repro.obs.precision.PrecisionStats` — export with
  ``trainer.precision.snapshot()`` or
  ``trainer.precision.registry.prometheus()``); with
  ``tcfg.grad_stats=True`` the jitted step additionally returns per-layer
  grad amax / nonfinite / underflow-fraction arrays (fixed shapes, no
  host callbacks) that land in the same snapshot.  Set ``log_every=1``
  to capture every scale transition,
- puts its steps on the ``jax.profiler`` timeline: each step runs in a
  ``StepTraceAnnotation("train", step_num=<step>)`` holding the program
  spans ``data wait`` (the prefetch queue, or the source itself without
  prefetch), ``host sync`` (the ``log_every`` metrics fetch) and
  ``checkpoint``; the step's device ops carry the scopes of
  :mod:`repro.obs.trace` (``embed``, ``layers``, the mixer kinds,
  ``unembed_loss``, ``mpx``, ``optimizer``).  A model with expert-only
  blocks logs, at each sync, the routed assignments that reached its
  held experts and the busiest held expert's load over their mean.
  ``tcfg.jax_trace_dir`` brackets the run with a ``jax.profiler`` device
  trace.
"""
from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Optional

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig, RunConfig
from repro.data.pipeline import Prefetcher
from repro.obs.precision import PrecisionStats, grad_layer_names
from repro.obs.trace import profiler_trace, program_span
from repro.sharding import rules as R
from repro.train import state as S
from repro.train.steps import make_train_step

# metrics keys produced by per_layer_grad_summary — array-valued, routed
# to PrecisionStats instead of the scalar history
_PER_LAYER_KEYS = ("grad_amax_per_layer", "grad_nonfinite_frac_per_layer",
                   "grad_underflow_frac_per_layer")

PyTree = Any


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 100
    ckpt_keep: int = 3
    log_every: int = 10
    watchdog_s: float = 0.0        # 0 = disabled
    prefetch: int = 2
    grad_stats: bool = False       # per-layer grad telemetry in the step
    jax_trace_dir: Optional[str] = None   # jax.profiler trace around fit()


class WatchdogTimeout(RuntimeError):
    pass


class Trainer:
    def __init__(self, cfg: ModelConfig, run: RunConfig, optimizer, data,
                 tcfg: TrainerConfig, mesh=None):
        self.cfg, self.run, self.optimizer = cfg, run, optimizer
        self.tcfg = tcfg
        self.mesh = mesh
        self.data = Prefetcher(data, tcfg.prefetch) if tcfg.prefetch else data
        self.ckpt = (Checkpointer(tcfg.ckpt_dir, tcfg.ckpt_keep)
                     if tcfg.ckpt_dir else None)
        self._preempted = False
        self._prev_handlers = {}
        self.rules = R.rules_with(dict(cfg.rules_overrides))

        self.state_shardings = (
            S.state_shardings(cfg, run, optimizer, mesh) if mesh else None)
        step_fn = make_train_step(cfg, run, optimizer,
                                  grad_stats=tcfg.grad_stats)
        if mesh is not None:
            self._step = jax.jit(step_fn,
                                 in_shardings=(self.state_shardings, None),
                                 out_shardings=(self.state_shardings, None),
                                 donate_argnums=(0,))
        else:
            self._step = jax.jit(step_fn, donate_argnums=(0,))
        self.state = self._init_or_resume()
        self.metrics_history: list[dict] = []
        self.precision = PrecisionStats()
        self._layer_names = (grad_layer_names(self.state["params"])
                             if tcfg.grad_stats else [])

    # ------------------------------------------------------------------ init
    def _init_or_resume(self) -> PyTree:
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            abstract = S.abstract_state(self.cfg, self.run, self.optimizer)
            state, extra = self.ckpt.restore(
                abstract, shardings=self.state_shardings)
            if "data" in extra and hasattr(self.data, "load_state"):
                self.data.load_state(extra["data"])
            print(f"[trainer] resumed from step {int(state['step'])}")
            return state
        key = jax.random.key(self.run.seed)
        with R.axis_rules(self.mesh, self.rules):
            state = S.init_state(key, self.cfg, self.run, self.optimizer)
            if self.state_shardings is not None:
                state = jax.device_put(state, self.state_shardings)
        return state

    # ----------------------------------------------------------- preemption
    def _install_signals(self):
        def handler(signum, frame):
            self._preempted = True
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_handlers[sig] = signal.signal(sig, handler)
            except ValueError:
                pass  # non-main thread (tests)

    def _restore_signals(self):
        for sig, h in self._prev_handlers.items():
            signal.signal(sig, h)

    def _checkpoint(self, sync: bool = False):
        if self.ckpt is None:
            return
        extra = {}
        if hasattr(self.data, "state"):
            extra["data"] = self.data.state()
        with program_span("checkpoint"):
            step = int(jax.device_get(self.state["step"]))
            if sync:
                self.ckpt.save(step, self.state, extra)
            else:
                self.ckpt.save_async(step, self.state, extra)

    def _next_batch(self) -> dict:
        if isinstance(self.data, Prefetcher):
            return self.data.next_batch()    # opens its own "data wait"
        with program_span("data wait"):
            return self.data.next_batch()

    def _log_step(self, step: int, metrics: dict, t0: float) -> None:
        """Fetch a logged step's metrics (the host sync) and record them."""
        m, layers = {}, {}
        for k, v in metrics.items():
            arr = np.asarray(v)
            if arr.ndim == 0:
                m[k] = float(arr)
            elif k in _PER_LAYER_KEYS:
                layers[k] = arr
        m["step"] = step + 1
        m["step_time_s"] = time.time() - t0
        self.metrics_history.append(m)
        self.precision.record_step(
            step + 1, m.get("loss_scale", 1.0),
            m.get("grads_finite", 1.0) >= 0.5)
        if layers:
            self.precision.record_layer_summary(self._layer_names, layers)
        experts = ""
        if "moe_held_assignments" in m:
            experts = (f"held={m['moe_held_assignments']:.0f} "
                       f"rows={m['moe_dispatch_rows']:.0f} "
                       f"load_max={m['moe_held_load_max']:.2f} ")
        print(f"[trainer] step {step+1} "
              f"loss={m['loss']:.4f} "
              f"scale={m.get('loss_scale', 1):.0f} {experts}"
              f"({m['step_time_s']*1e3:.0f}ms)")

    # ------------------------------------------------------------------ fit
    def fit(self) -> list[dict]:
        self._install_signals()
        try:
            start = int(jax.device_get(self.state["step"]))
            ctx = R.axis_rules(self.mesh, self.rules)
            with ctx, profiler_trace(self.tcfg.jax_trace_dir):
                for step in range(start, self.tcfg.total_steps):
                    with jax.profiler.StepTraceAnnotation(
                            "train", step_num=step + 1):
                        if self._train_one(step):
                            return self.metrics_history
            self._checkpoint(sync=True)
            return self.metrics_history
        finally:
            if self.ckpt is not None:
                self.ckpt.wait()
            if hasattr(self.data, "close"):
                self.data.close()
            self._restore_signals()

    def _train_one(self, step: int) -> bool:
        """Run step ``step`` (0-based); True when preempted and
        checkpointed, so that ``fit`` returns."""
        t0 = time.time()
        batch = self._next_batch()
        self.state, metrics = self._step(self.state, batch)
        if self.tcfg.log_every and (step + 1) % self.tcfg.log_every == 0:
            with program_span("host sync"):
                self._log_step(step, metrics, t0)
        dt = time.time() - t0
        if self.tcfg.watchdog_s and dt > self.tcfg.watchdog_s:
            self._checkpoint(sync=True)
            raise WatchdogTimeout(
                f"step {step+1} took {dt:.1f}s > "
                f"{self.tcfg.watchdog_s}s — checkpointed; "
                "relaunch to resume")
        if self.ckpt is not None and (step + 1) % self.tcfg.ckpt_every == 0:
            self._checkpoint()
        if self._preempted:
            print("[trainer] preemption signal — checkpointing")
            self._checkpoint(sync=True)
            return True
        return False
