"""Driver for ``train`` traffic: the Trainer's own jitted step and prefetch.

Set-up builds one ``Trainer`` the way ``python -m repro.launch.train``
does (a data mesh over the cell's chips, the job's RunConfig, a token
feed behind the trainer's prefetch thread), puts the benchmark's weights
into its state, and drives it through its first
``ref_steps`` steps: those compile the step, and they are what the
reference follows.  The window then drives the same object on, syncing
every ``sync_every`` steps as the launcher's logging does, until
``--seconds`` have passed at a sync.  The traced run adds
``trace_steps`` steps under the profiler after the window.

Numbers compared with the float32 reference (``checks``):

- ``loss_gap``: worst relative gap of the first steps' losses;
- ``grad_norm_gap``: the first step's pre-clip global gradient norm;
- ``grad_leaf_gap``: the first gradient as the optimizer got it (its
  first moment after one step over ``1 - beta1``), by the worst leaf:
  the gap between the two norms of a leaf over the larger of the
  reference's norm of that leaf and of the median leaf;
- ``change_leaf_gap``: the same for each leaf's change over the first
  steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).
"""
from __future__ import annotations

import dataclasses
import os
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, trace_reduce, traffic, weights

GRAD_FLOOR = 1e-3       # leaves below this share of the median gradient
FEED_STREAM = 1 << 20   # traffic.rng_for stream of the feed's step 0


def synthetic_batch(seed: int, step: int, batch: int, seq: int, vocab: int):
    """The feed's batch ``step`` (0-based): uniform token ids from a
    stream of its own, so that no two steps share rows; inputs, then
    targets.  (The program's ``SyntheticTokens`` keys consecutive steps
    by consecutive Philox counters, whose streams overlap: its batch
    ``k + 1`` is batch ``k`` shifted by eight tokens.)"""
    rng = traffic.rng_for(seed, FEED_STREAM + step)
    inputs = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
    targets = rng.integers(0, vocab, (batch, seq), dtype=np.int32)
    return inputs, targets


class Feed:
    """The benchmark's token source, in the batch layout the trainer's
    prefetch thread expects from ``SyntheticTokens``."""

    def __init__(self, seed: int, batch: int, seq: int, vocab: int):
        self.seed, self.batch, self.seq, self.vocab = seed, batch, seq, vocab
        self.step = 0

    def next_batch(self) -> dict:
        inputs, targets = synthetic_batch(self.seed, self.step, self.batch,
                                          self.seq, self.vocab)
        self.step += 1
        return {"inputs": inputs, "targets": targets}


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst |‖a‖ - ‖b‖| / max(‖b‖, median ‖b‖) over the leaves kept."""
    names = [n for n in ref if keep is None or keep(n)]
    med = statistics.median(ref[n] for n in names)
    return max(abs(prog[n] - ref[n]) / max(ref[n], med) for n in names)


def _faulty(step, faults):
    """The step with a planted fault (tests prove ``correct`` fails)."""
    if "state_unchanged" in faults:
        inner = step
        step = jax.jit(lambda s, b: (s, inner(s, b)[1]))
    if "half_batch" in faults:
        inner2 = step
        step = lambda s, b: inner2(  # noqa: E731
            s, {k: v[:v.shape[0] // 2] for k, v in b.items()})
    return step


def run(run: harness.Run) -> harness.Outcome:
    from repro.configs.base import RunConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer
    from repro.optim import make_optimizer
    from repro.sharding import rules
    from repro.train.trainer import Trainer, TrainerConfig

    cell, log, seed = run.cell, run.log, run.seed
    job = cell.traffic
    cfg = harness.program_config(cell.config)
    rc = dataclasses.replace(RunConfig(), seed=seed, **job["run"])
    batch, seq = job["batch"], job["seq"]
    data = Feed(seed, batch, seq, cfg.vocab_size)
    trainer = Trainer(cfg, rc, make_optimizer(rc), data,
                      TrainerConfig(total_steps=2 ** 31 - 1,
                                    log_every=job["sync_every"],
                                    prefetch=job["prefetch"]),
                      mesh=make_host_mesh(data=len(run.devices)))
    log("trainer built")
    shapes = transformer.param_shapes(cfg)
    params = weights.make(shapes, seed, jnp.float32)
    trainer.state["params"] = jax.device_put(
        params, trainer.state_shardings["params"])
    p0 = jax.tree.map(jnp.copy, trainer.state["params"])
    jax.block_until_ready(p0)
    del params
    log("weights made on the device")
    step = _faulty(trainer._step, run.faults)

    first = []
    with rules.axis_rules(trainer.mesh, trainer.rules):
        for k in range(job["ref_steps"]):
            trainer.state, met = step(trainer.state, trainer.data.next_batch())
            first.append({n: float(met[n]) for n in
                          ("loss", "grad_norm", "grads_finite",
                           "loss_scale")})
            if k == 0:
                grad1 = harness.leaf_norms(trainer.state["opt_state"]["mu"])
            log(f"set-up step {k + 1}: {first[-1]}")
        change = harness.leaf_norms(jax.tree.map(
            jnp.subtract, trainer.state["params"], p0))
        del p0

        # the window: the same object, synced as the launcher logs
        built = harness.compiles()
        t0 = time.perf_counter()
        setup_s = t0 - run.t_start
        log(f"window opens; setup_s {setup_s:.3f}")
        steps, finite = 0, []
        while True:
            for _ in range(job["sync_every"]):
                trainer.state, met = step(trainer.state,
                                          trainer.data.next_batch())
                finite.append(met["grads_finite"])
            steps += job["sync_every"]
            float(met["loss"])                        # the sync
            elapsed = time.perf_counter() - t0
            if elapsed >= run.seconds:
                break
        built = harness.compiles() - built
        finite = float(jnp.sum(jnp.stack(finite)))
        log(f"window: {steps} steps in {elapsed:.3f}s; last loss "
            f"{float(met['loss']):.4f}; {steps - finite:.0f} updates "
            f"skipped for non-finite gradients; loss scale "
            f"{float(met['loss_scale']):.0f}; "
            f"{built} programs built in the window")
        layer = {}
        if run.trace:
            layer = _traced(run, trainer, step, cfg, job)
    memory = harness.memory_peak(run.devices)
    trainer.data.close()
    del trainer, step, met
    checks, control = _check(run, shapes, first, grad1, change)
    return harness.Outcome(
        setup_s=setup_s,
        e2e={"train_tokens_per_s": steps * batch * seq / elapsed},
        attempted=steps, failed=int(round(steps - finite)), checks=checks,
        memory_peak_bytes=memory, layer=layer, control=control)


def _traced(run, trainer, step, cfg, job) -> dict:
    harness.clear_traces(run.trace_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.block_until_ready(trainer.state)
    jax.profiler.start_trace(run.trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        for _ in range(job["trace_steps"]):
            with jax.profiler.TraceAnnotation("next_batch"):
                batch = trainer.data.next_batch()
            with jax.profiler.TraceAnnotation("train_step dispatch"):
                trainer.state, _ = step(trainer.state, batch)
        jax.block_until_ready(trainer.state)
    jax.profiler.stop_trace()
    trace = trace_reduce.load(run.trace_dir)
    harness.clear_traces(run.trace_dir)
    tokens = job["trace_steps"] * job["batch"] * job["seq"]
    return {"trace": trace, "flops": tokens * flops_per_token(run.cell),
            "peaks": run.peaks}


def flops_per_token(cell: harness.Cell) -> float:
    """The model's training operations a token, by the configuration's
    ``costs`` module."""
    return cell.costs.train_flops_per_token(cell.config["model"],
                                            cell.traffic["seq"])


def _numbers(losses, grad_norm, grad, change, ref, moved) -> dict:
    """The numbers ``correct`` compares, of one side against the
    reference: its first steps' losses, its first pre-clip gradient
    norm, its first gradient by leaf and its change by leaf."""
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(losses, ref["losses"])),
        "grad_norm_gap": abs(grad_norm - ref["first_grad_norm"])
        / ref["first_grad_norm"],
        "grad_leaf_gap": leaf_gap(grad, ref["grad"]),
        "change_leaf_gap": leaf_gap(change, ref["change"], keep=moved),
    }


def _side(steps: dict, params0) -> tuple:
    """A reference run's numbers in the program's terms."""
    return (steps["losses"], steps["first_grad_norm"],
            harness.leaf_norms(steps["first_grad"]),
            harness.leaf_norms(jax.tree.map(jnp.subtract, steps["params"],
                                            params0)))


def _check(run, shapes, first, grad1, change) -> tuple:
    """The program's first steps against the float32 reference; with
    ``run.control``, the float8 control's too: (checks, control)."""
    cell, seed, job = run.cell, run.seed, run.cell.traffic
    ref_mod = harness.reference_module(cell.config)
    vocab = cell.config["model"]["vocab_size"]
    batches = [synthetic_batch(seed, k, job["batch"], job["seq"], vocab)
               for k in range(job["ref_steps"])]
    params0 = weights.make(shapes, seed, jnp.float32)
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        steps = ref_mod.adamw_steps(params0, batches, cell.config, job["run"])
    run.log(f"reference: {len(batches)} steps in "
            f"{time.perf_counter() - t:.3f}s; losses {steps['losses']}")
    losses, norm, grad, moved_by = _side(steps, params0)
    del steps
    ref = {"losses": losses, "first_grad_norm": norm, "grad": grad,
           "change": moved_by}
    med = statistics.median(grad.values())
    moved = lambda n: grad[n] >= GRAD_FLOOR * med  # noqa: E731
    b1 = job["run"]["beta1"]
    checks = _numbers([s["loss"] for s in first], first[0]["grad_norm"],
                      {n: v / (1 - b1) for n, v in grad1.items()}, change,
                      ref, moved)
    control = {}
    if run.control:
        with jax.default_matmul_precision("highest"):
            steps = ref_mod.adamw_steps(params0, batches, cell.config,
                                        job["run"], fp8=True)
        control = _numbers(*_side(steps, params0), ref, moved)
        run.log(f"float8 control: losses {steps['losses']}")
    return checks, control
