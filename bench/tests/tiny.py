"""Tiny cells for the CPU tests: a throwaway checkout of ``bench/`` with
small configurations, made from files alone, and a way to drive a run
without the harness's look for a chip."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

TINY_CONFIGS = {
    "tiny-ssd": {
        "registry": "mamba2-130m", "reference": "mamba2", "costs": "ssd_lm",
        "published": {"norm_epsilon": 1e-5},
        "model": {"n_layers": 2, "d_model": 64, "vocab_size": 512,
                  "ssm_state": 16, "ssm_headdim": 16, "ssm_expand": 2,
                  "ssm_chunk": 32, "remat": "full"}},
    "tiny-gqa": {
        "registry": "starcoder2-3b", "reference": "dense_lm",
        "costs": "dense_lm",
        "published": {"norm_epsilon": 1e-5},
        "model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 512, "rope_theta": 10000.0, "mlp": "gelu"}},
}

TINY_TRAFFIC = {
    "tiny_train": {
        "kind": "train", "batch": 4, "seq": 64, "sync_every": 2,
        "prefetch": 2, "ref_steps": 3, "trace_steps": 2,
        "run": {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95,
                "weight_decay": 0.1, "grad_clip": 1.0}},
    "tiny_open": {
        "kind": "serve_open",
        "engine": {"n_slots": 4, "page_size": 8, "chunk_size": 8,
                   "max_seq": 64, "kv_dtype": "bf16",
                   "prefix_cache": False, "pages_per_block": 2},
        "prompt_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 32},
        "new_tokens": {"median": 6, "sigma": 0.5, "min": 2, "max": 16},
        "rate_per_s": 20.0, "preroll_s": 0.2, "grace_s": 30,
        "trace_s": 0.2, "check": {"min_tokens": 96, "max_requests": 8}},
}

TINY_WORKLOADS = [("tiny-ssd.train", "tiny-ssd", "tiny_train"),
                  ("tiny-gqa.open", "tiny-gqa", "tiny_open")]


def make_root(tmp: str, limits: dict | None = None) -> str:
    """A checkout holding a copy of ``bench/`` plus the tiny cells,
    added as files and entries only."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = harness.load_spec(ROOT)
    for name, conf in TINY_CONFIGS.items():
        path = f"bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(dict(conf, name=name), f)
        spec["configs"].append({"name": name, "source": "test",
                                "file": path, "reduced": [], "why": "test"})
    for name, mix in TINY_TRAFFIC.items():
        with open(os.path.join(root, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    for name, conf, mix in TINY_WORKLOADS:
        spec["workloads"].append({"name": name, "config": conf,
                                  "traffic": mix, "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            real = [w for w in m.get("workloads", [])
                    if w.endswith("." + _REAL[mix])]
            if real:
                m["workloads"].append(name)
        for key, entries in _OWN.get(mix, {}).items():
            have = {m["name"]: m for m in spec[key]}
            for m in entries:
                if m["name"] not in have:
                    spec[key].append(dict(m, workloads=[name]))
        lim = (limits or {}).get(name, _LIMITS[mix])
        with open(os.path.join(root, "bench", "limits", name + ".json"),
                  "w") as f:
            json.dump(lim, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


_REAL = {"tiny_train": "pretrain_2k", "tiny_open": "chat_open"}


def _metric(name, unit, better, **kw) -> dict:
    return dict(name=name, unit=unit, better=better, **kw)


#: the serving metrics a tiny open-loop cell reports, entered with the
#: cell where BENCHMARK.json holds no open-loop cell to lend them
_OWN = {"tiny_open": {
    "end_to_end": [
        _metric("ttft_p90_ms", "ms", "lower", bound=0.25,
                source="host_clock"),
        _metric("itl_p95_ms", "ms", "lower", bound=0.25,
                source="host_clock")],
    "per_layer": [
        _metric("serve_step.mfu.open", "%", "higher", source="device_trace",
                layer="model step", moves="itl_p95_ms"),
        _metric("paged_attention_roofline.open", "%", "higher",
                source="device_trace", layer="kernels", moves="itl_p95_ms"),
        _metric("engine.host_ms_per_tick.open", "ms", "lower",
                source="program_span", layer="serving engine",
                moves="itl_p95_ms"),
        _metric("scheduler.queue_wait_ms_p90", "ms", "lower",
                source="host_clock", layer="serving engine",
                moves="ttft_p90_ms"),
        _metric("device.idle_share.open", "%", "lower",
                source="device_trace", layer="device",
                moves="itl_p95_ms")]}}
#: set as the real limits are, from readings of these tiny cells on the
#: CPU (six seeds each, program and float8 control): training's program
#: read at most grad norm 1.1e-3, grad leaf 0.043, change 0.011, and its
#: control on the seed the tests use (11) 0.012, 0.044, 0.029; serving's
#: program at most 0.0021, its control at least 0.021 (samples of 96
#: served tokens or more).  The same numbers are compared as in the real
#: cells.  At this size the float8 control's training gaps overlap the
#: program's on some seeds, so the control test runs on seed 11.
_LIMITS = {"tiny_train": {"grad_norm_gap": 3e-3, "grad_leaf_gap": 0.06,
                          "change_leaf_gap": 0.015},
           "tiny_open": {"logit_gap": 0.008}}


def make_run(root: str, workload: str, seed: int, seconds: float,
             trace: bool = False, faults=frozenset(),
             control: bool = False) -> harness.Run:
    """What a driver is given, with the look for a chip skipped."""
    import jax
    cell = harness.resolve(harness.load_spec(root), workload, root=root)
    return harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                       t_start=time.perf_counter(), devices=jax.devices()[:1],
                       peaks={"bf16_flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9},
                       log=lambda msg: None,
                       trace_dir=os.path.join(root, ".bench_traces"),
                       faults=frozenset(faults), control=control)


def drive(root: str, workload: str, seed: int, seconds: float,
          trace: bool = False, faults=frozenset(), control: bool = False):
    """A run with the look for a chip skipped: (result line, outcome)."""
    run = make_run(root, workload, seed, seconds, trace, faults, control)
    cell = run.cell
    out = cell.driver.run(run)
    if trace:
        return None, out
    return harness.result_line(cell, run, out, run.devices), out
