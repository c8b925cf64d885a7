"""Loop-based reference numerics for the quant ops (test oracles).

Deliberately naive: numpy loops over slots/tokens/pages, one page at a
time, mirroring the *contract* of :mod:`repro.quant.ops` (write-quantize
with per-page/per-head amax requantization, read-dequantize) without any
of the vectorized gather/scatter machinery.  ``tests/test_quant.py``
asserts the vectorized ops match these exactly.
"""
from __future__ import annotations

from typing import Union

import numpy as np

import jax.numpy as jnp

from repro.quant.formats import KVFormat, resolve
from repro.quant.ops import SCALE_FLOOR


def quantize_ref(x: np.ndarray, scale: float,
                 fmt: Union[str, KVFormat]) -> np.ndarray:
    """Scalar-scale quantization of one group, loop-reference semantics.

    int8 is pure numpy.  The fp8 grid cast goes through the SAME jnp
    primitive the op uses: XLA's CPU fp8 cast double-rounds through f16
    at exact grid midpoints (a ~half-ulp tie-break difference from
    ml_dtypes' numpy cast on a handful of values), and this reference
    exists to pin the paging/amax/requantization *contract* — the
    rounding primitive itself is covered by the round-trip error-bound
    tests, which hold under either tie-break.
    """
    fmt = resolve(fmt)
    scaled = np.clip(np.asarray(x, np.float32) / np.float32(scale),
                     -fmt.fmax, fmt.fmax)
    if fmt.kind == "int":
        return np.rint(scaled).astype(np.int8)
    return np.asarray(jnp.asarray(scaled).astype(fmt.grid_dtype)
                      .astype(jnp.float32))


def dequantize_ref(q: np.ndarray, scale: float) -> np.ndarray:
    return np.asarray(q, np.float32) * np.float32(scale)


def roundtrip_ref(x: np.ndarray, fmt: Union[str, KVFormat]) -> np.ndarray:
    """amax-scale -> quantize -> dequantize one group (fp32 out)."""
    fmt = resolve(fmt)
    scale = max(float(np.max(np.abs(np.asarray(x, np.float32)))) / fmt.fmax,
                SCALE_FLOOR)
    return dequantize_ref(quantize_ref(x, scale, fmt), scale)


def quantized_paged_write_ref(pages: np.ndarray, scales: np.ndarray,
                              vals: np.ndarray, page_table: np.ndarray,
                              positions: np.ndarray, valid: np.ndarray, *,
                              page_size: int, fmt: Union[str, KVFormat],
                              ) -> tuple[np.ndarray, np.ndarray]:
    """Token-at-a-time reference of :func:`repro.quant.ops
    .quantized_paged_write`: dequantize the touched page, splice, fresh
    amax per (page, head), requantize — in plain loops.

    Returns ``(pages, scales)`` with the pages' *grid values* as fp32
    (int levels for i8, fp8-grid values for the float formats) — compare
    against the vectorized op's pages via ``.astype(float32)``.
    """
    fmt = resolve(fmt)
    n_pages, n_kv, ps, d = pages.shape
    pages = np.asarray(jnp.asarray(pages).astype(jnp.float32)).copy()
    scales = np.asarray(scales, np.float32).copy()
    b, c = positions.shape

    # dequantized image of every touched page, keyed by physical index;
    # rows at positions >= the owning slot's write end are zeroed (they
    # are unreachable through the slot's length mask and may hold a
    # prior tenant's or a rejected window's stale values — the fresh
    # amax must not see them)
    touched: dict[int, np.ndarray] = {}
    for s in range(b):
        end = int(positions[s, 0]) + int(valid[s])
        for t in range(int(valid[s])):
            pos = int(positions[s, t])
            logical = pos // ps
            phys = int(page_table[s, logical])
            if phys >= n_pages:
                continue
            if phys not in touched:
                x = pages[phys] * scales[phys][:, None, None]
                for r in range(ps):
                    if logical * ps + r >= end:
                        x[:, r] = 0.0
                touched[phys] = x
            touched[phys][:, pos % ps] = np.asarray(vals[s, t], np.float32)

    for phys, x in touched.items():
        for h in range(n_kv):
            amax = float(np.max(np.abs(x[h])))
            scale = max(amax / fmt.fmax, SCALE_FLOOR)
            q = quantize_ref(x[h], scale, fmt)
            pages[phys][h] = np.asarray(q, np.float32)
            scales[phys, h] = scale
    return pages, scales
