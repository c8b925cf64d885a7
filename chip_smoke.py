#!/usr/bin/env python3
"""Smoke run of the repository's two main paths on a TPU — not a benchmark.

    python3 chip_smoke.py              # train + serve, one chip
    python3 chip_smoke.py --chips 4    # train only: (data=2, model=2) mesh
                                       # vs the same steps on one device

Train phase: ``mamba2-130m`` at its published widths through
``repro.train.trainer.Trainer`` (what ``python -m repro.launch.train``
drives) with the default ``RunConfig`` — fp32 params, bf16 compute,
dynamic loss scaling, AdamW — on ``SyntheticTokens`` batches of
8 x 2048 tokens.  It checks that every loss is finite, that every step's
gradients were finite (so every update was applied), and that the first
step's loss agrees with an fp32 forward on the same parameters and batch
at ``highest`` matmul precision.

Serve phase: ``starcoder2-3b`` at its published widths through
``repro.serve.ServeEngine(use_kernel=True)`` — every layer is full
attention, so every layer runs the Pallas paged-attention kernel — with
bf16 weights made on the device.  It checks that 8 seeded requests
finish "ok" with exactly ``max_new`` tokens, that the page pool's
invariants hold, that the compiled step holds the kernel
(``tpu_custom_call``), that the kernel and the gather path agree on the
logits of one fixed mixed prefill/decode step (at full depth, and cut
to ``LOGIT_DEPTH`` layers), and that the kernel
matches its oracle on random pools in every KV format the engine
accepts on the device.

Numbers go on labelled lines.  The last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when JAX finds a TPU and every check passed; otherwise the script exits
non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import mpx, quant, serve  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.data.pipeline import SyntheticTokens  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.kernels.paged_attention import paged_attention  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.quant import ops as qops  # noqa: E402
from repro.train.trainer import Trainer, TrainerConfig  # noqa: E402

#: |bf16-policy loss - fp32 loss| / fp32 loss on the first step.  bf16
#: rounds at 2^-9 relative per op; the loss is a mean over 16k tokens,
#: so rounding mostly averages out.  A broken cast or an unscaled loss
#: (2^15 with the default initial scale) is off by orders of magnitude.
LOSS_REL_TOL = 1e-2
#: per-step |loss(4 devices) - loss(1 device)| / loss(1 device): the mesh
#: changes only the order of bf16 reductions
MESH_LOSS_REL_TOL = 1e-2
#: kernel vs gather logits on the fixed mixed step, two checks.  Each
#: path rounds differently in bf16, and the difference grows with depth
#: as bf16 noise does: on CPU, kernel vs gather measure 0.65 % / 0.79 % /
#: 1.06 % relative RMS at 1 / 4 / 16 layers of starcoder2-3b widths, the
#: same as the gather path's own bf16-vs-fp32 difference (0.7 % at one
#: layer).  A wrong page, mask or scale moves the logits by tens of
#: percent.  So at full depth the relative RMS difference is checked
#: against LOGIT_RMS_TOL, and on the model cut to LOGIT_DEPTH layers
#: every logit is checked against the bound of the CPU kernel-vs-gather
#: logits test, |dlogit| <= LOGIT_TOL * (1 + |logit|) (the logits are
#: bf16, whose step is 0.031 at |logit| >= 4).
LOGIT_RMS_TOL = 3e-2
LOGIT_TOL = 3e-2
LOGIT_DEPTH = 1
#: kernel vs oracle on random bf16 / dequantized pools (bf16 compute)
KERNEL_TOL = 3e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def train_phase(cfg, *, batch: int = 8, seq: int = 2048, steps: int = 10,
                devices=None, mesh_shape=(1, 1), reference: bool = True,
                log=print) -> dict:
    """``steps`` Trainer steps of ``cfg`` on a (data, model) mesh over
    ``devices`` (default: the first device)."""
    devices = list(devices or jax.devices()[:1])
    mesh = make_mesh(mesh_shape, ("data", "model"), devices=devices)
    run = RunConfig()
    data = SyntheticTokens(cfg, batch=batch, seq=seq, seed=run.seed)
    trainer = Trainer(cfg, run, make_optimizer(run), data,
                      TrainerConfig(total_steps=steps, log_every=1),
                      mesh=mesh)
    out = {"n_params": sum(x.size for x in
                           jax.tree.leaves(trainer.state["params"]))}
    # bytes of train state each device holds: sharding made visible
    per_dev = {d.id: 0 for d in devices}
    for leaf in jax.tree.leaves(trainer.state):
        for shard in getattr(leaf, "addressable_shards", []):
            per_dev[shard.device.id] += shard.data.nbytes
    out["state_bytes_per_device"] = per_dev
    out["state_bytes_total"] = sum(
        getattr(x, "nbytes", 0) for x in jax.tree.leaves(trainer.state))
    if reference:
        # fp32 policy, same parameters and first batch, before the step
        # donates them
        first = SyntheticTokens(cfg, batch=batch, seq=seq,
                                seed=run.seed).next_batch()
        loss_fn = T.make_loss_fn(cfg, run.moe_aux_weight)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda p, b: loss_fn(p, b)[0])(
                trainer.state["params"], first)
        out["ref_loss_fp32"] = float(ref)
    hist = trainer.fit()
    out["losses"] = [m["loss"] for m in hist]
    out["grads_finite"] = [m["grads_finite"] for m in hist]
    out["loss_scale"] = [m["loss_scale"] for m in hist]
    out["step_time_s"] = [m["step_time_s"] for m in hist]
    out["bytes_in_use"] = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                           for d in devices}
    out["peak_bytes"] = {d.id: _peak_bytes(d) for d in devices}
    for m in hist:
        log(f"smoke train: step {m['step']} loss={m['loss']:.6f} "
            f"grads_finite={m['grads_finite']:.0f} "
            f"loss_scale={m['loss_scale']:.0f} "
            f"time={m['step_time_s']:.4f}s")
    check(len(out["losses"]) == steps, f"{len(out['losses'])} of {steps} "
          f"steps logged")
    check(all(np.isfinite(out["losses"])), f"nonfinite loss: "
          f"{out['losses']}")
    check(all(g >= 0.5 for g in out["grads_finite"]),
          f"a step skipped its update (grads_finite={out['grads_finite']})")
    if reference:
        rel = abs(out["losses"][0] - out["ref_loss_fp32"]) / abs(
            out["ref_loss_fp32"])
        out["ref_rel_err"] = rel
        log(f"smoke train: step 1 loss {out['losses'][0]:.6f} vs fp32 "
            f"{out['ref_loss_fp32']:.6f} (rel {rel:.2e}, bound "
            f"{LOSS_REL_TOL:.0e})")
        check(rel <= LOSS_REL_TOL, f"first-step loss off the fp32 "
              f"reference by {rel:.3e} > {LOSS_REL_TOL}")
    return out


def mesh_train_phase(cfg, *, batch: int = 8, seq: int = 2048,
                     steps: int = 10, devices=None, log=print) -> dict:
    """The train phase on a (data=2, model=2) mesh over four devices,
    then on the first device alone; per-step losses must match."""
    devices = list(devices or jax.devices()[:4])
    check(len(devices) == 4, f"--chips 4 needs 4 devices, got "
          f"{len(devices)}")
    sharded = train_phase(cfg, batch=batch, seq=seq, steps=steps,
                          devices=devices, mesh_shape=(2, 2),
                          reference=False, log=log)
    total = sharded["state_bytes_total"]
    for dev_id, n in sharded["state_bytes_per_device"].items():
        log(f"smoke mesh: device {dev_id} holds {n} of {total} train-state "
            f"bytes; bytes_in_use={sharded['bytes_in_use'][dev_id]}")
    check(max(sharded["state_bytes_per_device"].values()) < total,
          "a device holds the whole train state: nothing is sharded")
    single = train_phase(cfg, batch=batch, seq=seq, steps=steps,
                         devices=devices[:1], reference=False,
                         log=lambda *_: None)
    rel = [abs(a - b) / abs(b)
           for a, b in zip(sharded["losses"], single["losses"])]
    for i, (a, b) in enumerate(zip(sharded["losses"], single["losses"])):
        log(f"smoke mesh: step {i + 1} loss 2x2={a:.6f} 1x1={b:.6f}")
    log(f"smoke mesh: max rel loss diff {max(rel):.2e} (bound "
        f"{MESH_LOSS_REL_TOL:.0e})")
    check(max(rel) <= MESH_LOSS_REL_TOL, f"sharded losses drift from one "
          f"device by {max(rel):.3e}")
    return {"sharded": sharded, "single": single, "max_rel_diff": max(rel)}


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def init_bf16_params(cfg, seed: int = 0):
    """Random weights made on the device in bf16 under one jit, so the
    fp32 draw never sits beside the bf16 copy in device memory."""
    return jax.jit(lambda k: mpx.cast_to_bfloat16(T.init_params(k, cfg)))(
        jax.random.key(seed))


def _mixed_step_delta(cfg, params, *, n_slots: int, max_seq: int,
                      page_size: int, chunk: int, seed: int) -> dict:
    """Kernel vs gather-path logits on one mixed step: slot 0 decodes
    after a long prefix, slot 1 prefills a full chunk after a short one,
    slot 2 prefills from position 0, the rest idle.  The prefixes are
    written first, through the gather path, into pages scattered by a
    random permutation.  Returns the max and relative RMS of |dlogit|,
    max |logit| and the worst |dlogit| / (LOGIT_TOL * (1 + |logit|))."""
    rng = np.random.default_rng(seed)
    pmax = max_seq // page_size
    n_pages = n_slots * pmax
    table = rng.permutation(n_pages).astype(np.int32).reshape(n_slots, pmax)
    prefix = np.zeros(n_slots, np.int32)
    prefix[0] = max_seq // 2 - 7                    # mid-page
    prefix[1] = 2 * page_size + 3
    final_valid = np.zeros(n_slots, np.int32)
    final_valid[:3] = [1, chunk, min(chunk, 20)]

    def fwd(use_kernel):
        return jax.jit(lambda p, pg, tb, tk, st, vl: T.serve_forward(
            p, cfg, pg, tb, tk, st, vl, page_size=page_size,
            use_kernel=use_kernel))

    gather, kernel = fwd(False), fwd(True)
    pages = T.init_paged_cache(cfg, n_pages, page_size)
    ctx = rng.integers(1, cfg.vocab_size, (n_slots, max_seq)).astype(
        np.int32)
    tbl = jnp.asarray(table)
    for lo in range(0, int(prefix.max()), chunk):
        valid = np.clip(prefix - lo, 0, chunk).astype(np.int32)
        toks = np.zeros((n_slots, chunk), np.int32)
        for b in range(n_slots):
            toks[b, :valid[b]] = ctx[b, lo:lo + valid[b]]
        _, pages = gather(params, pages, tbl, jnp.asarray(toks),
                          jnp.full((n_slots,), lo, jnp.int32),
                          jnp.asarray(valid))
    toks = np.zeros((n_slots, chunk), np.int32)
    for b in range(n_slots):
        toks[b, :final_valid[b]] = ctx[b, prefix[b]:prefix[b]
                                       + final_valid[b]]
    args = (tbl, jnp.asarray(toks), jnp.asarray(prefix),
            jnp.asarray(final_valid))
    lg, _ = gather(params, pages, *args)
    lk, _ = kernel(params, pages, *args)
    live = final_valid > 0
    lg = np.asarray(lg, np.float32)[live]
    lk = np.asarray(lk, np.float32)[live]
    check(np.isfinite(lg).all() and np.isfinite(lk).all(),
          "nonfinite logits on the mixed step")
    delta = np.abs(lk - lg)
    return {"max_delta": float(delta.max()),
            "rel_rms": float(np.sqrt((delta ** 2).mean() / (lg ** 2).mean())),
            "max_logit": float(np.abs(lg).max()),
            "worst_ratio": float((delta / (LOGIT_TOL * (1 + np.abs(lg))))
                                 .max())}


def kernel_formats_check(cfg, *, page_size: int, chunk: int, seed: int,
                         log=print) -> dict:
    """The paged kernel vs its oracle on random pools at ``cfg``'s
    attention widths, in every KV format the engine accepts on the
    device: ragged slots, a chunk and decode tokens, an idle slot."""
    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, pmax = 4, 8
    n_pages = b * pmax
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(n_pages).astype(np.int32)
                        .reshape(b, pmax))
    start = jnp.asarray([3 * page_size + 5, 11, 0, 0], jnp.int32)
    valid = jnp.asarray([1, chunk, 1, 0], jnp.int32)
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (b, chunk, h, d), jnp.bfloat16)
    kf = jax.random.normal(ks[1], (n_pages, kv, page_size, d), jnp.float32)
    vf = jax.random.normal(ks[2], (n_pages, kv, page_size, d), jnp.float32)
    device = jax.devices()[0]
    interpret = device.platform != "tpu"
    out = {}
    for name in sorted(quant.FORMATS):
        if name in quant.TPU_UNSUPPORTED and device.platform == "tpu":
            continue
        fmt = quant.resolve(name)
        if fmt.quantized:
            sk = qops.amax_scale(kf, fmt, axes=(2, 3))
            sv = qops.amax_scale(vf, fmt, axes=(2, 3))
            kp = qops.quantize(kf, sk[:, :, None, None], fmt)
            vp = qops.quantize(vf, sv[:, :, None, None], fmt)
            got = jax.jit(lambda *a: paged_attention(
                *a[:6], k_scales=a[6], v_scales=a[7], interpret=interpret))(
                q, kp, vp, table, start, valid, sk, sv)
            want = kref.quantized_paged_attention_ref(
                q, kp, vp, sk, sv, table, start, valid)
        else:
            kp, vp = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
            got = jax.jit(lambda *a: paged_attention(
                *a, interpret=interpret))(q, kp, vp, table, start, valid)
            want = kref.paged_attention_ref(q, kp, vp, table, start, valid)
        err = float(np.abs(np.asarray(got, np.float32)
                           - np.asarray(want, np.float32)).max())
        out[name] = err
        log(f"smoke serve: kernel vs oracle kv={name} max|d|={err:.3e} "
            f"(bound {KERNEL_TOL:.0e})")
        check(err <= KERNEL_TOL, f"kernel off its oracle in kv={name} by "
              f"{err:.3e}")
    return out


def serve_phase(cfg, *, n_slots: int = 8, max_seq: int = 2048,
                page_size: int = 16, chunk: int = 32, n_requests: int = 8,
                prompt_len=(100, 1000), max_new: int = 32, seed: int = 0,
                log=print) -> dict:
    """Serve ``n_requests`` seeded prompts through
    ``ServeEngine(use_kernel=True)`` and check the results."""
    out = {}
    t0 = time.perf_counter()
    params = init_bf16_params(cfg, seed)
    jax.block_until_ready(params)
    out["n_params"] = sum(x.size for x in jax.tree.leaves(params))
    out["init_s"] = time.perf_counter() - t0
    engine = serve.ServeEngine(cfg, params, n_slots=n_slots,
                               max_seq=max_seq, page_size=page_size,
                               chunk_size=chunk, use_kernel=True)
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_len[0], prompt_len[1] + 1, n_requests)
    for n in lens:
        engine.submit(rng.integers(1, cfg.vocab_size, int(n)).tolist(),
                      max_new=max_new)
    results, step_s = [], []
    while len(results) < n_requests:
        check(len(step_s) < 100 * n_requests, "engine made no progress")
        t = time.perf_counter()
        results += engine.step()
        step_s.append(time.perf_counter() - t)
    out["step_time_s"] = step_s
    out["prompt_lens"] = [int(n) for n in lens]
    out["statuses"] = [r.status for r in results]
    out["n_tokens"] = [len(r.tokens) for r in results]
    log(f"smoke serve: {len(step_s)} engine steps; first (incl. compile) "
        f"{step_s[0]:.3f}s, median of the rest "
        f"{float(np.median(step_s[1:])):.4f}s; prompts {out['prompt_lens']}")
    log(f"smoke serve: statuses {out['statuses']} tokens {out['n_tokens']}")
    check(all(s == "ok" for s in out["statuses"]),
          f"request statuses {out['statuses']}")
    check(all(n == max_new for n in out["n_tokens"]),
          f"token counts {out['n_tokens']} != {max_new}")
    engine.cache.check_invariants()
    # the engine's own step program, at the shapes it ran
    cache = engine.cache
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    hlo = engine._device_step.lower(
        params, cache.pages, cache.table_device(), z(n_slots, chunk),
        z(n_slots), z(n_slots), z(n_slots, 1), z(n_slots, 0), z(n_slots),
        jnp.zeros((n_slots,), bool), jax.random.key(0)).compile().as_text()
    out["tpu_custom_call"] = "tpu_custom_call" in hlo
    log(f"smoke serve: compiled step holds tpu_custom_call: "
        f"{out['tpu_custom_call']}")
    del engine, cache
    shape = dict(n_slots=n_slots, max_seq=max_seq, page_size=page_size,
                 chunk=chunk, seed=seed)
    out["logits_full_depth"] = d = _mixed_step_delta(cfg, params, **shape)
    log(f"smoke serve: kernel vs gather on the mixed step, {cfg.n_layers} "
        f"layers: max|dlogit|={d['max_delta']:.3e} (max|logit| "
        f"{d['max_logit']:.3f}); relative RMS {d['rel_rms']:.3e} (bound "
        f"{LOGIT_RMS_TOL:.0e})")
    check(d["rel_rms"] <= LOGIT_RMS_TOL, f"kernel vs gather logits differ "
          f"by {d['rel_rms']:.3e} relative RMS at full depth")
    del params
    cut = dataclasses.replace(cfg, n_layers=LOGIT_DEPTH)
    out["logits"] = d = _mixed_step_delta(cut, init_bf16_params(cut, seed),
                                          **shape)
    log(f"smoke serve: kernel vs gather on the mixed step, {LOGIT_DEPTH} "
        f"layer(s): max|dlogit|={d['max_delta']:.3e} (max|logit| "
        f"{d['max_logit']:.3f}); worst |dlogit| / ({LOGIT_TOL:.0e} * "
        f"(1 + |logit|)) = {d['worst_ratio']:.3f} (bound 1)")
    check(d["worst_ratio"] <= 1.0, f"kernel vs gather logits differ by "
          f"{d['max_delta']:.3e}, {d['worst_ratio']:.3f}x the bound")
    out["kernel_formats"] = kernel_formats_check(
        cfg, page_size=page_size, chunk=chunk, seed=seed, log=log)
    out["peak_bytes"] = _peak_bytes(jax.devices()[0])
    return out


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the train phase, on a (data=2, model=2) "
                         "mesh, compared with one device")
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devices)} "
              f"{dev.platform} device(s)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    print(f"chip_smoke: smoke run, not a benchmark; {len(devices)} x "
          f"{dev.device_kind}; compile cache {enable_compile_cache()}")
    try:
        t0 = time.perf_counter()
        train_cfg = registry.get_config("mamba2-130m")
        if args.chips == 4:
            out = mesh_train_phase(train_cfg, devices=devices[:4])
            print(f"smoke mesh: wall {time.perf_counter() - t0:.1f}s")
            count = 4
        else:
            out = train_phase(train_cfg)
            print(f"smoke train: {out['n_params']} params; first step "
                  f"(incl. compile) {out['step_time_s'][0]:.3f}s; "
                  f"peak_bytes_in_use {out['peak_bytes']}; wall "
                  f"{time.perf_counter() - t0:.1f}s")
            t0 = time.perf_counter()
            out = serve_phase(registry.get_config("starcoder2-3b"))
            print(f"smoke serve: {out['n_params']} params; weight init "
                  f"{out['init_s']:.1f}s; peak_bytes_in_use "
                  f"{out['peak_bytes']}; wall "
                  f"{time.perf_counter() - t0:.1f}s")
            check(out["tpu_custom_call"],
                  "the compiled serve step holds no tpu_custom_call")
            count = len(devices)
    except SmokeFailure as err:
        print(f"chip_smoke: FAILED: {err}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
