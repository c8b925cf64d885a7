"""Multi-head attention: GQA, RoPE, sliding windows, logit softcap, caches.

Four execution paths share one set of parameters:

- ``attend_splash``  — on a TPU, causal self-attention with no window or
  softcap runs through the Pallas splash-attention kernel (GQA as grouped
  query heads over each KV head), whose backward kernels keep O(S) memory
  per head: the training path at 8k positions and beyond.
- ``attend_plain``   — masked einsum softmax; small sequences (<= 4k).
- ``attend_blocked`` — query-block × key-block streaming softmax with fp32
  running (max, sum, acc) state.  This is the flash-attention recurrence
  expressed in pure jnp so it lowers/partitions under GSPMD for the 512-chip
  dry-run; causal/window key blocks that are fully masked are *statically
  skipped* (the query loop is a Python loop over static slices), so long
  prefills don't pay the 2× dense-causal FLOP tax and never materialize an
  (S, S) score tensor.  The Pallas kernel in ``repro/kernels/flash_attention``
  is the TPU-native version of exactly this loop.
- ``decode_step``    — single-token query against a (possibly rolling) KV
  cache.

Precision follows the paper: QK^T and PV matmuls run in the compute dtype
(bf16/fp16 on the MXU), softmax statistics and accumulators are fp32
(``force_full_precision`` / explicit fp32 state).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro import mpx
from repro.nn.norms import softcap as apply_softcap
from repro.nn.param import ParamSpec
from repro.nn.rope import apply_rope
from repro.sharding.rules import shard

NEG_INF = -1e30  # fp32 additive mask value (not -inf: avoids NaN on all-masked rows)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def attention_spec(d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
                   qkv_bias: bool = False, out_bias: bool = False):
    spec = {
        "wq": ParamSpec((d_model, n_heads, head_dim),
                        ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d_model, n_kv_heads, head_dim),
                        ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d_model, n_kv_heads, head_dim),
                        ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((n_heads, head_dim, d_model),
                        ("heads", "head_dim", "embed"),
                        fan_in=n_heads * head_dim),
    }
    if qkv_bias:
        spec["bq"] = ParamSpec((n_heads, head_dim), ("heads", "head_dim"),
                               init="zeros")
        spec["bk"] = ParamSpec((n_kv_heads, head_dim),
                               ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = ParamSpec((n_kv_heads, head_dim),
                               ("kv_heads", "head_dim"), init="zeros")
    if out_bias:
        spec["bo"] = ParamSpec((d_model,), ("embed",), init="zeros")
    return spec


def _project_qkv(params, x, positions, theta):
    """x (B,S,d) -> q (B,S,H,D), k/v (B,S,K,D); RoPE applied if theta > 0."""
    dtype = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if "bq" in params:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    if theta > 0:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    q = shard(q, ("batch", "seq", "heads", "head_dim"))
    k = shard(k, ("batch", "seq", "kv_heads", "head_dim"))
    v = shard(v, ("batch", "seq", "kv_heads", "head_dim"))
    return q, k, v


def _expand_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """(B,S,K,D) -> (B,S,H,D) by repeating each KV head H/K times."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


# --------------------------------------------------------------------------
# plain path (short sequences)
# --------------------------------------------------------------------------

def _mask_bias(q_pos, k_pos, causal: bool, window: int) -> jnp.ndarray:
    """(Sq, Sk) fp32 additive mask from position vectors (fused by XLA)."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), jnp.bool_)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attend_plain(q, k, v, *, causal: bool, window: int, cap: float,
                 q_positions=None, k_positions=None) -> jnp.ndarray:
    """q (B,Sq,H,D), k/v (B,Sk,K,D), K divides H -> (B,Sq,H,D).

    GQA runs as grouped einsums WITHOUT materializing H-expanded K/V —
    expanding first costs an H/K-times-inflated KV gather (and a matching
    fp32 dK reduction in backward) on meshes where heads don't shard
    (§Perf iteration B-3).
    """
    b, sq, h, d = q.shape
    kv = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    sk = k.shape[1]
    qg = q.reshape(b, sq, kv, h // kv, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * scale
    if cap > 0:
        scores = apply_softcap(scores, cap)
    q_pos = q_positions if q_positions is not None else jnp.arange(sq)
    k_pos = k_positions if k_positions is not None else jnp.arange(sk)
    bias = _mask_bias(q_pos, k_pos, causal, window)
    probs = mpx.force_full_precision(jax.nn.softmax, q.dtype)(
        scores.astype(jnp.float32) + bias, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


# --------------------------------------------------------------------------
# blocked path (long sequences): streaming softmax, static block skipping
# --------------------------------------------------------------------------

def attend_blocked(q, k, v, *, causal: bool, window: int, cap: float,
                   q_block: int = 2048, k_block: int = 2048) -> jnp.ndarray:
    """Flash-style blocked attention in pure jnp (self-attention, aligned
    positions).  fp32 running max/sum/accumulator; bf16 matmuls."""
    b, s, h, d = q.shape
    assert k.shape[1] == s, "blocked path is for self-attention"
    scale = 1.0 / math.sqrt(d)
    q_block = min(q_block, s)
    k_block = min(k_block, s)
    n_q = (s + q_block - 1) // q_block
    outs = []
    for qi in range(n_q):
        q_lo, q_hi = qi * q_block, min((qi + 1) * q_block, s)
        qb = q[:, q_lo:q_hi]                                   # (B,Qb,H,D)
        # static key range for this query block
        k_hi = q_hi if causal else s
        k_lo = max(0, q_lo - window + 1) if window > 0 else 0
        k_lo = (k_lo // k_block) * k_block                     # align
        acc = jnp.zeros((b, q_hi - q_lo, h, d), jnp.float32)
        m = jnp.full((b, h, q_hi - q_lo), NEG_INF, jnp.float32)
        l = jnp.zeros((b, h, q_hi - q_lo), jnp.float32)
        q_pos = jnp.arange(q_lo, q_hi)
        for kj_lo in range(k_lo, k_hi, k_block):
            kj_hi = min(kj_lo + k_block, k_hi)
            kb = k[:, kj_lo:kj_hi]
            vb = v[:, kj_lo:kj_hi]
            scores = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) * scale
            scores = scores.astype(jnp.float32)
            if cap > 0:
                scores = cap * jnp.tanh(scores / cap)
            k_pos = jnp.arange(kj_lo, kj_hi)
            need_mask = (causal and kj_hi > q_lo) or window > 0
            if need_mask:
                scores = scores + _mask_bias(q_pos, k_pos, causal, window)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            correction = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[..., None])             # (B,H,Qb,Kb)
            l = l * correction + p.sum(axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vb)
            acc = acc * correction.transpose(0, 2, 1)[..., None] + pv
            m = m_new
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
        outs.append(out.astype(q.dtype))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

#: sequences above this use the blocked path (never materialize S×S scores)
BLOCKED_THRESHOLD = 8192
#: splash kernel tile (query and key rows) in every phase
SPLASH_BLOCK = 512


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _splash_fits(s: int, causal: bool, window: int, cap: float) -> bool:
    """The splash kernel takes this call: a TPU, causal, no window or
    softcap, whole tiles, and no mesh to partition it over."""
    from repro.sharding import rules as _R
    mesh, _ = _R._get_ctx()
    return (_on_tpu() and causal and window == 0 and cap <= 0
            and s % min(SPLASH_BLOCK, s) == 0 and s % 128 == 0
            and (mesh is None or mesh.size == 1))


def _splash_kernel(heads: int, seq: int):
    """A causal splash kernel, made at each trace: the mask arrays it
    holds are constants of the trace that makes them."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    blk = min(SPLASH_BLOCK, seq)
    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))] * heads)
    sizes = sk.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=blk,
                          block_q_dkv=blk, block_kv_dkv=blk,
                          block_kv_dkv_compute=blk, block_q_dq=blk,
                          block_kv_dq=blk)
    return sk.make_splash_mha_single_device(mask, block_sizes=sizes)


def attend_splash(q, k, v) -> jnp.ndarray:
    """Causal self-attention through the splash kernel: q (B,S,H,D),
    k/v (B,S,K,D) with K dividing H -> (B,S,H,D).  The kernel takes
    head-major rows and unscaled logits, so q is scaled first."""
    b, s, h, d = q.shape
    kernel = _splash_kernel(h, s)
    qh = (q * jnp.asarray(1.0 / math.sqrt(d), q.dtype)).transpose(0, 2, 1, 3)
    out = jax.vmap(kernel)(qh, k.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)


def attention_apply(params, x, *, n_heads: int, causal: bool, window: int,
                    cap: float, rope_theta: float,
                    positions: Optional[jnp.ndarray] = None,
                    use_blocked: Optional[bool] = None) -> jnp.ndarray:
    """Self-attention over x (B,S,d) -> (B,S,d)."""
    s = x.shape[1]
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(params, x, positions, rope_theta)
    blocked = use_blocked if use_blocked is not None else s > BLOCKED_THRESHOLD
    # expanded-KV path only where heads shard cleanly over the model axis
    # (the reshape in the grouped path would cross shard boundaries there);
    # grouped path everywhere else — it avoids the H/K-inflated KV gather.
    from repro.sharding import rules as _R
    mesh, _ = _R._get_ctx()
    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    heads_shard = msize > 1 and n_heads % msize == 0
    if use_blocked is None and _splash_fits(s, causal, window, cap):
        out = attend_splash(q, k, v)
    elif blocked:
        out = attend_blocked(q, _expand_kv(k, n_heads),
                             _expand_kv(v, n_heads),
                             causal=causal, window=window, cap=cap)
    elif heads_shard:
        out = attend_plain(q, _expand_kv(k, n_heads), _expand_kv(v, n_heads),
                           causal=causal, window=window, cap=cap)
    else:
        out = attend_plain(q, k, v, causal=causal, window=window, cap=cap)
    out = shard(out, ("batch", "seq", "heads", "head_dim"))
    y = jnp.einsum("bqhd,hdm->bqm", out, params["wo"].astype(x.dtype))
    if "bo" in params:
        y = y + params["bo"].astype(x.dtype)
    return shard(y, ("batch", "seq", "embed"))


# --------------------------------------------------------------------------
# decode (KV cache)
# --------------------------------------------------------------------------

def init_cache_spec(batch: int, max_seq: int, n_kv_heads: int, head_dim: int,
                    window: int, dtype) -> dict:
    """Abstract cache layout for one attention layer.

    Local-attention layers store a rolling buffer of ``window`` positions —
    this is what makes mixtral/gemma2/recurrentgemma long-context decode
    sub-quadratic in memory.
    """
    length = min(max_seq, window) if window > 0 else max_seq
    return {
        "k": jax.ShapeDtypeStruct((batch, length, n_kv_heads, head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, length, n_kv_heads, head_dim), dtype),
    }


def init_cache(batch, max_seq, n_kv_heads, head_dim, window, dtype):
    spec = init_cache_spec(batch, max_seq, n_kv_heads, head_dim, window, dtype)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec,
                        is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))


def decode_step(params, cache, x, pos, *, n_heads: int, window: int,
                cap: float, rope_theta: float):
    """One decode step.  x (B,1,d), pos scalar int32 -> (y (B,1,d), cache').

    The cache seq dim is a rolling buffer for windowed layers
    (slot = pos mod window); full-attention layers write at ``pos``.
    Positions beyond ``pos`` are masked via a stored-position comparison,
    which also handles the rolling wrap-around correctly.
    """
    dtype = x.dtype
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, positions, rope_theta)
    length = cache["k"].shape[1]
    slot = pos % length if window > 0 else pos
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(dtype), slot, axis=1)
    new_cache = {"k": k, "v": v}

    kx = _expand_kv(k, n_heads)
    vx = _expand_kv(v, n_heads)
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kx,
                        preferred_element_type=jnp.float32) * scale
    if cap > 0:
        scores = cap * jnp.tanh(scores / cap)
    # stored position of each slot (rolling-buffer aware)
    idx = jnp.arange(length)
    if window > 0:
        # slot i currently holds position: the latest p <= pos with p % length == i
        stored = pos - ((pos - idx) % length)
        valid = (stored >= 0) & (stored > pos - window) & (stored <= pos)
    else:
        stored = idx
        valid = idx <= pos
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vx)
    y = jnp.einsum("bqhd,hdm->bqm", out, params["wo"].astype(dtype))
    if "bo" in params:
        y = y + params["bo"].astype(dtype)
    return y, new_cache


# --------------------------------------------------------------------------
# paged KV cache (serving): fixed-size pages + per-slot page tables
# --------------------------------------------------------------------------
#
# The serving engine (``repro.serve``) replaces the monolithic per-slot
# (B, max_seq, K, D) cache slab with a shared pool of fixed-size pages:
# each slot owns a row of a page table mapping logical page -> physical
# page, so HBM is committed per admitted request, not per slot capacity.
# Pools are head-major, (P, K, page_size, D): token position p of slot b,
# kv head h lives at pages[table[b, p // page_size], h, p % page_size], so
# one (page, head) is a contiguous (page_size, D) tile — the block shape
# the TPU's tiling rules accept in the kernel.  Unallocated table entries
# hold the sentinel ``n_pages`` (writes there are dropped).
#
# Attention reads the pool two ways.  The native path
# (``use_kernel=True``) is the Pallas paged-attention kernel
# (``repro.kernels.paged_attention``): its block index maps walk each
# slot's page table directly, so only allocated pages are streamed and no
# contiguous copy of the cache ever exists.  The fallback
# (``paged_gather`` + masked softmax) materializes each slot's padded
# prefix as a dense (B, Pmax*page_size, K, D) view — sentinel entries
# read clamped garbage that is masked by position.  The fallback is the
# numerics oracle and the non-TPU / windowed / softcapped path, not the
# serving layout.
#
# Pools may store sub-bf16 (``kv_format`` in {"i8", "f8_e4m3",
# "f8_e3m4"}, see ``repro.quant``): values live on the format's grid
# with a (P, K) fp32 amax-scale sidecar per pool.  Writes quantize
# (``quant.ops.quantized_pool_write`` requantizes exactly the touched
# pages), the kernel dequantizes block-by-block in VMEM, and the gather
# fallback dequantizes its dense view right after gathering.

def paged_cache_spec(n_pages: int, page_size: int, n_kv_heads: int,
                     head_dim: int, dtype, kv_format: str = "bf16") -> dict:
    """Abstract paged K/V pool layout for one attention layer.

    ``kv_format`` "bf16" is the passthrough {"k", "v"} pair in ``dtype``;
    quantized formats add the {"k_scale", "v_scale"} fp32 sidecars and
    store the pools in the format's storage dtype (``repro.quant``)."""
    from repro.quant import formats as qfmt
    return qfmt.pool_spec(n_pages, page_size, n_kv_heads, head_dim,
                          kv_format, dtype=dtype)


def paged_write(pages: jnp.ndarray, vals: jnp.ndarray,
                page_table: jnp.ndarray, positions: jnp.ndarray,
                valid: jnp.ndarray, *, page_size: int) -> jnp.ndarray:
    """Scatter ``vals`` (B, C, K, D) into head-major ``pages`` (P, K, ps, D).

    ``positions`` (B, C) are absolute token positions, ``valid`` (B,) the
    number of real tokens per slot (suffix is padding).  Padding tokens and
    slots whose table entry is the sentinel scatter out of bounds and are
    dropped.

    Ownership contract: the caller must hold every targeted physical page
    *exclusively* — this scatter mutates rows in place and knows nothing
    about sharing.  Under prefix caching (``PagedStatePool`` with
    ``prefix_cache=True``) pages can be referenced by several slots'
    tables at once; the pool's ``note_write``/COW machinery copies any
    shared page and repoints the writing slot *before* the write is
    flushed, so by the time this function runs every targeted page has
    refcount 1 again.  Bypassing the pool's write path breaks that
    guarantee silently.
    """
    n_pages = pages.shape[0]
    b, c = positions.shape
    phys = jnp.take_along_axis(page_table, positions // page_size, axis=1)
    off = positions % page_size
    ok = jnp.arange(c)[None, :] < valid[:, None]
    phys = jnp.where(ok, phys, n_pages)                      # OOB -> dropped
    return pages.at[phys.reshape(-1), :, off.reshape(-1)].set(
        vals.reshape((b * c,) + vals.shape[2:]), mode="drop")


def paged_gather(pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """(P, K, ps, D), (B, Pmax) -> contiguous view (B, Pmax*ps, K, D).

    The gather-based *fallback* layout for attention: a dense padded copy
    of every slot's prefix, sentinel entries reading clamped garbage that
    the caller masks by length.  The serving hot path never calls this —
    ``paged_attend(use_kernel=True)`` streams pages through the page
    table inside the Pallas kernel instead.
    """
    g = pages[page_table].swapaxes(2, 3)              # (B, Pmax, ps, K, D)
    b, pmax, ps = g.shape[:3]
    return g.reshape((b, pmax * ps) + g.shape[3:])


def paged_gather_scales(scales: jnp.ndarray, page_table: jnp.ndarray,
                        page_size: int) -> jnp.ndarray:
    """(P, K) sidecar, (B, Pmax) -> per-position scales (B, Pmax*ps, K).

    Companion to :func:`paged_gather` for quantized pools: every token of
    a page shares its page's per-head scale, so the gathered scale is
    broadcast over the ``page_size`` rows.  Fallback/oracle path only —
    the kernel selects each page's scale from a resident per-head row.
    """
    g = scales[page_table]                                # (B, Pmax, K)
    b, pmax, kv = g.shape
    return jnp.broadcast_to(g[:, :, None, :],
                            (b, pmax, page_size, kv)).reshape(
                                b, pmax * page_size, kv)


def paged_attend(params, pages: dict, page_table: jnp.ndarray,
                 x: jnp.ndarray, positions: jnp.ndarray, valid: jnp.ndarray,
                 *, page_size: int, n_heads: int, window: int, cap: float,
                 rope_theta: float, use_kernel: bool = False,
                 pages_per_block: int = 1, kv_format: str = "bf16"):
    """Chunked-prefill / decode attention against a paged KV cache.

    x (B, C, d) with per-token absolute ``positions`` (B, C) and ``valid``
    (B,) real-token counts.  Writes the chunk's K/V into the pages, then
    attends every query to its slot's full cached prefix, causal by
    absolute position.  C=1 with valid=1 is exactly single-token decode;
    C>1 is a prefill chunk, a speculative decode window (valid = 1 + k
    proposed tokens, verified causally in one pass — the same C>1 program
    as prefill), or a mixed-chunk serving step in which decode slots
    carry small valid and idle slots valid=0.  Returns
    (y (B, C, d), new ``pages`` dict).

    ``kv_format`` selects the pool storage precision (``repro.quant``):
    "bf16" writes/reads the pools as-is; "i8" / "f8_e4m3" / "f8_e3m4"
    quantize the chunk's K/V on write (per-page/per-head amax scales in
    the pool dict's ``k_scale`` / ``v_scale`` fp32 sidecars) and
    dequantize on read — in VMEM inside the kernel, or on the gathered
    view in the fallback.

    ``use_kernel=True`` runs the Pallas paged-attention kernel
    (:mod:`repro.kernels.paged_attention`) for full-attention layers: the
    page table is a scalar-prefetch operand (quantized scale sidecars
    ride VMEM as one resident row per kv head — they scale with the
    pool, so SMEM is the wrong home) and the kernel's block index
    maps stream each slot's allocated pages directly from the shared
    pool — the gathered
    contiguous (B, Pmax*page_size, K, D) copy is never formed, for
    decode AND prefill chunks alike, and quantized pools are multiplied
    back to the compute dtype block-by-block so no dense bf16 image of
    the cache exists either.  ``pages_per_block`` widens each kernel
    K-block to span that many logical pages (page_size 16 alone
    underfills the 128-lane MXU dim).  Sliding-window (``window > 0``)
    and softcapped (``cap > 0``) layers, and ``use_kernel=False``, take
    the pure-jnp gather fallback — the numerics oracle, which runs
    everywhere.
    """
    from repro.quant import formats as qfmt, ops as qops
    fmt = qfmt.resolve(kv_format)
    dtype = x.dtype
    q, k_new, v_new = _project_qkv(params, x, positions, rope_theta)
    if fmt.quantized:
        new_pages = qops.quantized_pool_write(
            pages, k_new, v_new, page_table, positions, valid,
            page_size=page_size, fmt=fmt)
    else:
        new_pages = {
            "k": paged_write(pages["k"], k_new.astype(dtype), page_table,
                             positions, valid, page_size=page_size),
            "v": paged_write(pages["v"], v_new.astype(dtype), page_table,
                             positions, valid, page_size=page_size),
        }
    if use_kernel and window == 0 and cap <= 0:
        from repro.kernels.paged_attention import paged_attention
        out = paged_attention(q, new_pages["k"], new_pages["v"], page_table,
                              positions[:, 0], valid,
                              k_scales=new_pages.get("k_scale"),
                              v_scales=new_pages.get("v_scale"),
                              pages_per_block=pages_per_block,
                              interpret=jax.default_backend() != "tpu")
    else:
        k = paged_gather(new_pages["k"], page_table)         # (B, S, K, D)
        v = paged_gather(new_pages["v"], page_table)
        if fmt.quantized:
            # scale sidecar gathered per page, broadcast over page rows —
            # the dense dequantized view exists ONLY on this oracle path
            ks = paged_gather_scales(new_pages["k_scale"], page_table,
                                     page_size)
            vs = paged_gather_scales(new_pages["v_scale"], page_table,
                                     page_size)
            k = qops.dequantize(k, ks[..., None], dtype)
            v = qops.dequantize(v, vs[..., None], dtype)
        kx = _expand_kv(k, n_heads)
        vx = _expand_kv(v, n_heads)
        scale = 1.0 / math.sqrt(q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kx,
                            preferred_element_type=jnp.float32) * scale
        if cap > 0:
            scores = cap * jnp.tanh(scores / cap)
        idx = jnp.arange(k.shape[1])
        ok = idx[None, None, :] <= positions[:, :, None]     # (B, C, S)
        if window > 0:
            ok &= idx[None, None, :] > positions[:, :, None] - window
        scores = jnp.where(ok[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs, vx)
    y = jnp.einsum("bqhd,hdm->bqm", out, params["wo"].astype(dtype))
    if "bo" in params:
        y = y + params["bo"].astype(dtype)
    return y, new_pages
