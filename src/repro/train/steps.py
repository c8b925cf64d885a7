"""The jitted train / serve steps with MPX mixed precision wired in.

``make_train_step`` is the paper's Example 2 embedded in a production step:

    scaling, finite, (loss, metrics), grads = mpx.filter_value_and_grad(
        loss_fn, scaling, has_aux=True)(params, batch)
    grads, gnorm = clip_by_global_norm(grads, ...)
    params, opt_state = mpx.optimizer_update(params, optimizer, opt_state,
                                             grads, finite)

plus: microbatched gradient accumulation (``run.grad_accum > 1``) with a
single unscale/finite-check/adjust at the end (cheaper and numerically
identical to per-microbatch handling), metrics, and step counting.

Device scopes (:func:`repro.obs.trace.scope`): casts, loss scaling,
unscale, the finite check and the scale adjust run under ``mpx``; the
clip or global norm, the optimizer update with its skip-step select
(fused into it) and the ZeRO grad sharder under ``optimizer``; the model
under its own scopes (:mod:`repro.models.transformer`).

``make_serve_step`` wraps the unified transformer's single-token decode with
greedy sampling — the function the decode/long-context dry-run cells lower.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import mpx
from repro.configs.base import ModelConfig, RunConfig
from repro.core.policy import Policy
from repro.models import transformer as tfm
from repro.obs.trace import scope
from repro.optim import clip_by_global_norm, global_norm

PyTree = Any


def _accum_grads(loss_fn, scaling, policy: Policy, params, batch, k: int,
                 unroll: bool = False, grad_sharder=None,
                 compress: bool = False):
    """Gradient accumulation over k microbatches via lax.scan.

    Each microbatch computes *scaled* bf16/fp16 gradients; the fp32
    accumulator sums them; one unscale + finite-check at the end.  The
    per-microbatch reduce-scatter of cotangents overlaps the next
    microbatch's compute under the XLA latency-hiding scheduler.
    """
    for leaf in jax.tree.leaves(batch):
        if getattr(leaf, "ndim", 0) and leaf.shape[0] % k:
            raise ValueError(
                f"grad_accum={k} does not divide the batch size "
                f"{leaf.shape[0]} (batch leaf shape {leaf.shape}); use a "
                f"global batch size that is a multiple of run.grad_accum")
    mb = jax.tree.map(
        lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]), batch)
    diff, static = mpx.partition(params, mpx.is_inexact_array)

    def scaled_loss(d, b):
        p = mpx.combine(d, static)
        if policy.is_mixed:
            with scope("mpx"):
                p = policy.cast_to_compute(p)
                b = policy.cast_to_compute(b)
        loss, metrics = loss_fn(p, b)
        with scope("mpx"):
            return scaling.scale(loss), (loss, metrics)

    def body(acc, b):
        (_, (loss, metrics)), g = jax.value_and_grad(
            scaled_loss, has_aux=True)(diff, b)
        if compress:
            # gradient compression: per-microbatch cotangents cross the DP
            # links in bf16 (half the reduce bytes); the accumulator stays
            # fp32 so the K-step sum keeps full precision — made safe by
            # the loss scaling this framework exists for.
            g = mpx.cast_tree(g, jnp.bfloat16)
        acc = jax.tree.map(
            lambda a, x: a + x.astype(jnp.float32) if mpx.is_inexact_array(a)
            else a, acc, g)
        if grad_sharder is not None:
            with scope("optimizer"):
                acc = grad_sharder(acc)  # ZeRO-2: reduce-scatter into shards
        return acc, (loss.astype(jnp.float32), metrics)

    acc0 = jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32)
        if mpx.is_inexact_array(x) else x, diff)
    if grad_sharder is not None:
        with scope("optimizer"):
            acc0 = grad_sharder(acc0)
    acc, (losses, metrics) = jax.lax.scan(body, acc0, mb,
                                          unroll=k if unroll else 1)
    with scope("mpx"):
        grads = scaling.unscale(acc)
        grads = jax.tree.map(
            lambda g: g / k if mpx.is_inexact_array(g) else g, grads)
        finite = mpx.all_finite(grads)
        new_scaling = scaling.adjust(finite)
    loss = losses.mean()
    metrics = jax.tree.map(lambda m: jnp.mean(m), metrics)
    return new_scaling, finite, (loss, metrics), grads


def make_train_step(cfg: ModelConfig, run: RunConfig, optimizer,
                    loss_fn: Callable | None = None,
                    grad_stats: bool = False) -> Callable:
    """Returns ``train_step(state, batch) -> (new_state, metrics)``.

    ``grad_stats=True`` adds the :mod:`repro.obs.precision` per-layer
    gradient summary (amax / nonfinite fraction / underflow fraction as
    fixed-shape ``(L,)`` fp32 arrays) to the metrics dict — computed
    inside the jitted step, no host callbacks, no extra syncs; layer
    names come from :func:`repro.obs.precision.grad_layer_names`.
    """
    policy = Policy.parse(run.policy)
    custom_loss = loss_fn is not None
    loss_fn = loss_fn or tfm.make_loss_fn(cfg, run.moe_aux_weight)
    grad_sharder = None
    if not custom_loss and run.zero1:
        from repro.train.state import make_grad_sharder
        grad_sharder = make_grad_sharder(cfg)

    def train_step(state: PyTree, batch: PyTree):
        scaling = state["scaling"]
        if run.grad_accum > 1:
            new_scaling, finite, (loss, metrics), grads = _accum_grads(
                loss_fn, scaling, policy, state["params"], batch,
                run.grad_accum, unroll=run.accum_unroll,
                grad_sharder=grad_sharder, compress=run.compress_grads)
        else:
            vag = mpx.filter_value_and_grad(
                loss_fn, scaling, has_aux=True,
                use_mixed_precision=policy.is_mixed,
                compute_dtype=policy.compute_dtype)
            new_scaling, finite, (loss, metrics), grads = vag(
                state["params"], batch)

        if grad_stats:
            # per-layer precision telemetry on the *unscaled, unclipped*
            # fp32 grads — the magnitudes §3.3's control loop reacts to
            from repro.obs.precision import per_layer_grad_summary
            layer_stats = per_layer_grad_summary(grads)
        else:
            layer_stats = {}

        with scope("optimizer"):
            if run.grad_clip > 0:
                grads, gnorm = clip_by_global_norm(grads, run.grad_clip)
            else:
                gnorm = global_norm(grads)

        if run.master_weights == "opt":
            # Megatron-style distributed optimizer: fp32 master weights live
            # data-sharded inside opt state; the working params are compute-
            # dtype and re-materialized (one gather) per applied step.
            opt_state = state["opt_state"]
            master = opt_state["master"]
            inner = {k: v for k, v in opt_state.items() if k != "master"}
            with scope("optimizer"):
                updates, inner_new = optimizer.update(grads, inner,
                                                      params=master)
                master_new = mpx.apply_updates(master, updates)
            with scope("mpx"):
                params_new = policy.cast_to_compute(master_new)
            with scope("optimizer"):
                params = mpx.select_tree(finite, params_new, state["params"])
                opt_new = {"master": master_new, **inner_new}
                opt_state = mpx.select_tree(finite, opt_new, opt_state)
        else:
            params, opt_state = mpx.optimizer_update(
                state["params"], optimizer, state["opt_state"], grads,
                finite)
        new_state = {"params": params, "opt_state": opt_state,
                     "scaling": new_scaling, "step": state["step"] + 1}
        out_metrics = {"loss": loss, "grad_norm": gnorm,
                       "grads_finite": finite.astype(jnp.float32),
                       "loss_scale": jnp.asarray(new_scaling.loss_scaling,
                                                 jnp.float32)}
        out_metrics.update(layer_stats)
        for k, v in metrics.items():
            out_metrics[k] = v
        return new_state, out_metrics

    return train_step


def make_eval_step(cfg: ModelConfig, run: RunConfig,
                   loss_fn: Callable | None = None) -> Callable:
    policy = Policy.parse(run.policy)
    loss_fn = loss_fn or tfm.make_loss_fn(cfg, run.moe_aux_weight)

    def eval_step(params, batch):
        p, b = params, batch
        if policy.is_mixed:
            p = policy.cast_to_compute(p)
            b = policy.cast_to_compute(b)
        loss, metrics = loss_fn(p, b)
        return loss.astype(jnp.float32), metrics

    return eval_step


def make_serve_step(cfg: ModelConfig, sampling=None) -> Callable:
    """``serve_step(params, cache, tokens, pos) -> (next_tokens, new_cache)``.

    Params are expected pre-cast to the serving dtype (bf16); sampling runs
    in fp32.  The default (``sampling=None`` or greedy
    :class:`~repro.serve.sampling.SamplingParams`) keeps the historical
    4-arg argmax signature — the function the ``decode_*`` / ``long_*``
    dry-run cells lower and compile.  With stochastic ``SamplingParams``
    the step takes a PRNG key: ``serve_step(params, cache, tokens, pos,
    key)``.

    This is the monolithic-slab serving step; the paged-KV-cache engine in
    :mod:`repro.serve` (which re-exports this) supersedes it for
    continuous-batching workloads.
    """
    if sampling is None or sampling.is_greedy:
        def serve_step(params, cache, tokens, pos):
            logits, new_cache = tfm.decode(params, cfg, cache, tokens, pos)
            next_tokens = jnp.argmax(logits.astype(jnp.float32), axis=-1)
            return next_tokens.astype(jnp.int32), new_cache

        return serve_step

    from repro.serve.sampling import make_sampler  # lazy: avoid import cycle
    sampler = make_sampler(sampling)

    def serve_step(params, cache, tokens, pos, key):
        logits, new_cache = tfm.decode(params, cfg, cache, tokens, pos)
        next_tokens, _ = sampler(logits[:, -1], key)   # ids only; the probs
        return next_tokens[:, None], new_cache         # feed spec verify

    return serve_step
