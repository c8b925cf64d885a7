"""Attention-path benchmark: plain vs blocked-XLA vs Pallas(interpret).

Wall time on CPU (indicative only) + compiled bytes for the memory-roofline
story: the blocked path never materializes the (S, S) score tensor, which
is what lets 32k-prefill cells fit HBM (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.nn import attention as A


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run() -> list[tuple[str, float, str]]:
    b, s, h, d = 1, 2048, 4, 64
    q = jax.random.normal(jax.random.key(0), (b, s, h, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.bfloat16)

    plain = jax.jit(lambda q, k, v: A.attend_plain(
        q, k, v, causal=True, window=0, cap=0.0))
    blocked = jax.jit(lambda q, k, v: A.attend_blocked(
        q, k, v, causal=True, window=0, cap=0.0, q_block=512, k_block=512))

    t_plain = _time(plain, q, k, v)
    t_blocked = _time(blocked, q, k, v)

    bytes_plain = float(jax.jit(plain).lower(q, k, v).compile()
                        .cost_analysis().get("bytes accessed", 0))
    bytes_blocked = float(jax.jit(blocked).lower(q, k, v).compile()
                          .cost_analysis().get("bytes accessed", 0))
    return [
        ("attention_plain_2k", t_plain * 1e6,
         f"bytes={bytes_plain/2**20:.0f}MiB"),
        ("attention_blocked_2k", t_blocked * 1e6,
         f"bytes={bytes_blocked/2**20:.0f}MiB "
         f"({bytes_plain/max(bytes_blocked,1):.2f}x fewer)"),
    ]
