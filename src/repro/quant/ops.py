"""Quantize/dequantize ops for the paged KV cache (write-quantize,
read-dequantize).

The serving contract is asymmetric:

- **Writes quantize.**  :func:`quantized_paged_write` scatters a chunk's
  new K/V values into the quantized page pools.  Because the scale is
  per *page* (per kv head) and pages fill incrementally — a decode step
  appends one token to a partially-filled page — a write is a
  read-modify-write of exactly the pages the chunk touches: gather those
  pages, dequantize with their current scales, splice the new bf16
  values in, recompute the page's amax, requantize the whole page with
  the new scale, scatter pages + scales back.  Untouched pages keep
  their bits and scales verbatim.  The number of touched pages per slot
  is a *static* function of the chunk width (a C-token contiguous range
  straddles at most ``(C - 1) // page_size + 2`` pages), so the gather
  stays a fixed tiny multiple of the chunk size — never the pool, never
  a slot's whole prefix.

- **Reads dequantize in the consumer.**  The paged-attention kernel
  multiplies the scales back onto K/V blocks in VMEM
  (:mod:`repro.kernels.paged_attention`); the gather fallback uses
  :func:`dequantize` on the gathered view.  Dequantization is the same
  two ops everywhere — ``q.astype(f32) * scale``, cast to the compute
  dtype — so kernel and oracle agree exactly.

Requantization error: re-rounding a page's existing values on each write
adds at most half an ulp *of the dequantized value* per write, and the
page's scale only changes when a new amax enters — bounded, and pinned by
the round-trip tests in ``tests/test_quant.py``.
"""
from __future__ import annotations

from typing import Union

import jax.numpy as jnp

from repro.quant.formats import KVFormat, resolve

#: scale floor — keeps ``x / scale`` finite for all-zero pages without
#: perturbing any real amax (bf16 subnormals bottom out ~1e-38).
SCALE_FLOOR = 1e-30


def quantize(x: jnp.ndarray, scale: jnp.ndarray,
             fmt: Union[str, KVFormat]) -> jnp.ndarray:
    """``x`` (any float) -> values on ``fmt``'s grid in its storage dtype.

    ``scale`` broadcasts against ``x`` (fp32).  int8 rounds to nearest
    (ties to even) and clips to ±127; fp8 rounds through the fp8 dtype
    (RTNE) after a ±fmax clip (e3m4 would otherwise overflow to inf on
    a half-ulp-above-max round).
    """
    fmt = resolve(fmt)
    if not fmt.quantized:
        raise ValueError(f"{fmt.name} is a passthrough format")
    scaled = x.astype(jnp.float32) / scale
    scaled = jnp.clip(scaled, -fmt.fmax, fmt.fmax)
    if fmt.kind == "int":
        return jnp.rint(scaled).astype(jnp.int8)
    return scaled.astype(fmt.grid_dtype).astype(fmt.storage_dtype())


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, out_dtype=jnp.float32,
               ) -> jnp.ndarray:
    """``q * scale`` in fp32, cast to ``out_dtype`` — THE dequant rule.

    The paged-attention kernel applies exactly this per K/V block in
    VMEM; keeping one definition makes kernel-vs-oracle comparisons
    meaningful at tight tolerances.
    """
    return (q.astype(jnp.float32) * scale).astype(out_dtype)


def amax_scale(x: jnp.ndarray, fmt: Union[str, KVFormat],
               axes) -> jnp.ndarray:
    """Per-group symmetric scale: ``max|x| / fmax`` over ``axes``,
    floored so a group of zeros quantizes (to zeros) without dividing
    by zero."""
    fmt = resolve(fmt)
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes)
    return jnp.maximum(amax / fmt.fmax, SCALE_FLOOR)


def max_write_pages(chunk: int, page_size: int, pmax: int) -> int:
    """Pages a ``chunk``-token contiguous positional range can straddle."""
    return min((max(chunk, 1) - 1) // page_size + 2, pmax)


def quantized_paged_write(pages: jnp.ndarray, scales: jnp.ndarray,
                          vals: jnp.ndarray, page_table: jnp.ndarray,
                          positions: jnp.ndarray, valid: jnp.ndarray, *,
                          page_size: int, fmt: Union[str, KVFormat],
                          ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantizing scatter of ``vals`` (B, C, K, D) into head-major
    ``pages`` (P, K, ps, D) with the ``(P, K)`` fp32 ``scales`` sidecar.

    ``positions`` (B, C) are absolute token positions (``positions[:, 0]``
    is the slot's chunk start, the serving layout), ``valid`` (B,) the
    real-token counts (0 = idle slot).  Touched pages are requantized
    with a fresh per-page/per-head amax; padding tokens, idle slots and
    sentinel table entries drop out of the scatter exactly like
    :func:`repro.nn.attention.paged_write`.  Returns ``(pages, scales)``
    with untouched pages bit-identical.

    Rows of a touched page at positions **at or beyond the slot's write
    end** (``start + valid``) are zeroed before the amax: attention can
    never read them (it masks by position), but they can hold garbage a
    previous *tenant* of the physical page left behind (``retire()``
    frees pages without clearing the device pool) or a rejected
    speculative tail — either would silently inflate the fresh amax and
    crush the live rows' precision.  Zeroing them makes a page's scale a
    function of exactly the values that are reachable through it.

    Ownership contract: this is a whole-page **read-modify-write** —
    every touched page is dequantized, merged and requantized against a
    fresh amax, so even rows this call doesn't write change bit pattern
    (same values, new scale).  A physical page shared across slots via
    prefix caching must therefore be copied-on-write *before* the
    requantizing scatter reaches it — not merely before its rows
    diverge — and the copy must carry the page's ``scales`` sidecar row
    along with the values.  ``PagedStatePool`` enforces exactly this
    (COW queued at admission boundaries and in ``note_write``, flushed
    before the device step); callers going around the pool must not
    target pages with refcount > 1.
    """
    fmt = resolve(fmt)
    n_pages = pages.shape[0]
    b, c = positions.shape
    ps = page_size
    pmax = page_table.shape[1]
    wp = max_write_pages(c, ps, pmax)

    start = positions[:, 0]
    first = start // ps                                        # (B,)
    last = (start + jnp.maximum(valid, 1) - 1) // ps
    j = jnp.arange(wp)[None, :]                                # (1, wp)
    logical = first[:, None] + j                               # (B, wp)
    live = (j <= (last - first)[:, None]) & (valid[:, None] > 0)
    phys = jnp.take_along_axis(page_table,
                               jnp.clip(logical, 0, pmax - 1), axis=1)
    phys = jnp.where(live, phys, n_pages)          # dead/sentinel -> OOB
    safe = jnp.clip(phys, 0, n_pages - 1)

    # gather the touched pages, dequantize with their current scales; the
    # splice below works token-major, (B, wp, ps, K, D)
    cur = pages[safe].swapaxes(2, 3)               # (B, wp, ps, K, D)
    cur_s = scales[safe]                           # (B, wp, K)
    x = dequantize(cur, cur_s[:, :, None, :, None])
    kd = x.shape[3:]

    # splice the chunk's new values in at page-local positions
    local = positions - (first * ps)[:, None]                  # (B, C)
    ok = jnp.arange(c)[None, :] < valid[:, None]
    local = jnp.where(ok, local, wp * ps)                      # OOB -> drop
    x = x.reshape((b, wp * ps) + kd)
    x = x.at[jnp.arange(b)[:, None], local].set(
        vals.astype(jnp.float32), mode="drop")
    # zero rows past the slot's write end: unreachable through THIS
    # slot's length mask, but possibly stale (prior tenant of a reused
    # page, rejected speculative tail) — they must not feed the amax
    row_pos = (first * ps)[:, None] + jnp.arange(wp * ps)[None, :]
    reachable = row_pos < (start + valid)[:, None]             # (B, wp*ps)
    x = jnp.where(reachable[(...,) + (None,) * len(kd)], x, 0.0)
    x = x.reshape((b, wp, ps) + kd)

    # fresh per-(page, head) amax over the whole page, requantize
    new_s = amax_scale(x, fmt, axes=(2, 4))                    # (B, wp, K)
    q = quantize(x, new_s[:, :, None, :, None], fmt)

    flat = phys.reshape(-1)
    pages = pages.at[flat].set(
        q.swapaxes(2, 3).reshape((-1,) + pages.shape[1:]), mode="drop")
    scales = scales.at[flat].set(
        new_s.astype(jnp.float32).reshape(-1, kd[0]), mode="drop")
    return pages, scales


def quantized_pool_write(pool: dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                         page_table: jnp.ndarray, positions: jnp.ndarray,
                         valid: jnp.ndarray, *, page_size: int,
                         fmt: Union[str, KVFormat]) -> dict:
    """One attention layer's write step: quantize K and V chunks into the
    ``{"k", "v", "k_scale", "v_scale"}`` container."""
    k, ks = quantized_paged_write(pool["k"], pool["k_scale"], k_new,
                                  page_table, positions, valid,
                                  page_size=page_size, fmt=fmt)
    v, vs = quantized_paged_write(pool["v"], pool["v_scale"], v_new,
                                  page_table, positions, valid,
                                  page_size=page_size, fmt=fmt)
    return {"k": k, "v": v, "k_scale": ks, "v_scale": vs}
