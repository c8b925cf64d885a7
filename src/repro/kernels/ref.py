"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth the kernel tests `assert_allclose` against
(shape/dtype sweeps, interpret=True execution of the kernels on CPU).
Everything here is deliberately simple — no blocking, no streaming — and
follows the MPX precision discipline: fp32 softmax/statistics, compute-dtype
matmuls.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0) -> jnp.ndarray:
    """q/k/v: (B, S, H, D) (same H — expand GQA before calling).

    fp32 scores/softmax, output cast back to q.dtype.
    """
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(s)[None, :]
    ok = jnp.ones((s, s), bool)
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= k_pos > q_pos - window
    scores = jnp.where(ok[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v)
    return out.astype(q.dtype)


def decode_attention_ref(q, k, v, lengths) -> jnp.ndarray:
    """One query token per slot vs a ragged KV cache (continuous batching).

    q (B, H, D), k/v (B, S, K, D) with K dividing H (GQA expanded here),
    ``lengths`` a scalar or (B,) vector of valid prefix lengths.  fp32
    scores/softmax, compute-dtype matmuls — the oracle for the ragged
    serving hot path in ``repro.kernels.decode_attention``.
    """
    b, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    ke = jnp.repeat(k, h // kv, axis=2)
    ve = jnp.repeat(v, h // kv, axis=2)
    scores = jnp.einsum("bhd,bshd->bhs", q, ke,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    lengths_b = jnp.asarray(lengths, jnp.int32).reshape(-1, 1, 1)
    scores = jnp.where(jnp.arange(s)[None, None, :] < lengths_b,
                       scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bshd->bhd", probs, ve)


def paged_attention_ref(q, k_pages, v_pages, page_table, start, valid
                        ) -> jnp.ndarray:
    """Ragged paged-attention oracle (gathers the pool; the kernel doesn't).

    q (B, C, H, D) — one serving chunk per slot; k_pages / v_pages
    (P, K, page_size, D) shared head-major pools; page_table (B, Pmax) int32 with the
    sentinel ``P`` in unallocated entries; start / valid (B,) chunk
    position and real-token count.  Query ``ci`` attends causally to
    cache positions ``<= start + ci``; padding positions (``ci >= valid``)
    and idle slots (``valid = 0``) return exact zeros — matching
    ``repro.kernels.paged_attention``.  fp32 scores/softmax, compute-dtype
    matmuls.  This is deliberately the dense gather-based layout: the
    ground truth the page-table-walking kernel is tested against.
    """
    b, c, h, d = q.shape
    n_pages, kv, ps, _ = k_pages.shape
    pmax = page_table.shape[1]
    s_max = pmax * ps
    tbl = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, n_pages - 1)
    k = k_pages[tbl].swapaxes(2, 3).reshape(b, s_max, kv, d)
    v = v_pages[tbl].swapaxes(2, 3).reshape(b, s_max, kv, d)
    ke = jnp.repeat(k, h // kv, axis=2)
    ve = jnp.repeat(v, h // kv, axis=2)
    start = jnp.asarray(start, jnp.int32)
    valid = jnp.asarray(valid, jnp.int32)
    kpos = jnp.arange(s_max)
    # zero V beyond each slot's length so fully-masked rows (uniform
    # softmax over NEG_INF scores) cannot pick up garbage-page values
    length = (start + valid)[:, None, None, None]
    ve = jnp.where(kpos[None, :, None, None] < length, ve, 0)
    scores = jnp.einsum("bchd,bshd->bhcs", q, ke,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    qpos = start[:, None] + jnp.arange(c)[None, :]
    ok = (kpos[None, None, :] <= qpos[:, :, None]) & \
         (jnp.arange(c)[None, :, None] < valid[:, None, None])
    scores = jnp.where(ok[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhcs,bshd->bchd", probs, ve)
    row_ok = jnp.arange(c)[None, :] < valid[:, None]
    return jnp.where(row_ok[:, :, None, None], out, 0).astype(q.dtype)


def quantized_paged_attention_ref(q, k_pages, v_pages, k_scales, v_scales,
                                  page_table, start, valid) -> jnp.ndarray:
    """Quantized ragged paged-attention oracle (``repro.quant`` pools).

    ``k_pages`` / ``v_pages`` hold int8 or fp8 (bf16-emulated off-TPU)
    values with ``(P, K)`` fp32 amax-scale sidecars ``k_scales`` /
    ``v_scales`` — the write-quantize/read-dequantize serving layout.
    Dequantizes each pool with the SAME per-element rule the kernel
    applies per block in VMEM (``repro.quant.ops.dequantize``: fp32
    multiply, cast to q.dtype) and defers to :func:`paged_attention_ref`,
    so any kernel/oracle disagreement is attention math, never a dequant
    discrepancy.  This is deliberately the dense gather-based layout the
    kernel exists to avoid — ground truth only.
    """
    from repro.quant.ops import dequantize
    k = dequantize(k_pages, k_scales[:, :, None, None], q.dtype)
    v = dequantize(v_pages, v_scales[:, :, None, None], q.dtype)
    return paged_attention_ref(q, k, v, page_table, start, valid)


def rmsnorm_ref(x, scale, eps: float = 1e-6) -> jnp.ndarray:
    """(..., D) RMSNorm with fp32 statistics, output in x.dtype."""
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 / rms) * scale.astype(jnp.float32)).astype(x.dtype)


def unscale_finite_ref(g, inv_scale) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused gradient unscale + isfinite reduction (one array).

    Returns (g * inv_scale as fp32, all_finite bool) — the per-leaf body of
    the MPX loss-scaling hot path.
    """
    g32 = g.astype(jnp.float32) * inv_scale
    return g32, jnp.all(jnp.isfinite(g32))
