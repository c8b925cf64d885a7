"""Training launcher CLI.

Runs real training (CPU-scale with smoke/reduced configs; on a TPU fleet the
same entry point drives the production mesh) with the full production stack:
MPX mixed precision + dynamic loss scaling, sharded state, data pipeline,
fault-tolerant trainer (checkpoint/resume/SIGTERM).

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 50 --batch 8 --seq 64 --ckpt-dir /tmp/run1
    # kill it mid-run, then relaunch the same command: resumes from latest.

Key=value overrides apply to RunConfig, e.g. ``--set learning_rate=1e-4
grad_accum=2 policy=params=float32,compute=float16,output=float32``.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import registry
from repro.configs.base import RunConfig
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import make_optimizer
from repro.train.trainer import Trainer, TrainerConfig


def _apply_overrides(run: RunConfig, pairs: list[str]) -> RunConfig:
    out = {}
    fields = {f.name: f.type for f in dataclasses.fields(RunConfig)}
    for pair in pairs:
        key, _, val = pair.partition("=")
        if key not in fields:
            raise SystemExit(f"unknown RunConfig field {key!r}")
        cur = getattr(run, key)
        out[key] = type(cur)(val) if not isinstance(cur, bool) \
            else val.lower() in ("1", "true", "yes")
    return dataclasses.replace(run, **out)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--set", nargs="*", default=[], metavar="K=V")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    run = _apply_overrides(RunConfig(), args.set)
    optimizer = make_optimizer(run)
    data = SyntheticTokens(cfg, batch=args.batch, seq=args.seq, seed=run.seed)

    trainer = Trainer(
        cfg, run, optimizer, data,
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, log_every=args.log_every),
        # every visible device, data parallel (ZeRO-1 shards the
        # optimizer state over the same axis)
        mesh=make_host_mesh(data=jax.device_count()))
    trainer.fit()


if __name__ == "__main__":
    main()
