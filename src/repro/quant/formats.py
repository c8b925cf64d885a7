"""Sub-bf16 KV storage formats: the dtype half of the ``kv=`` policy axis.

MPX treats precision as a *policy* threaded through a pipeline, not a
property baked into arrays.  On the serving side the policy axis that
matters is the KV cache: decode is HBM-bound on KV page reads (the paged
kernel already streams only allocated pages), so the next lever is the
*bytes per cached token*.  A :class:`KVFormat` names one storage format
for the paged KV pools:

- ``bf16``     — the passthrough baseline (2 bytes/elem, no scales);
- ``i8``       — symmetric int8 with per-page, per-head amax scales
                 (1 byte/elem + a tiny fp32 scale sidecar);
- ``f8_e4m3``  — fp8 e4m3 (4-bit exponent, 3-bit mantissa, max 448);
- ``f8_e3m4``  — fp8 e3m4 (3-bit exponent, 4-bit mantissa, max 15.5 —
                 one more mantissa bit for amax-scaled tensors whose
                 dynamic range the scale already absorbed).

Quantized formats store values *scaled into the format's representable
range*: ``scale = amax / fmax`` per (page, kv-head), ``q = round(x /
scale)`` on the format's value grid, ``x~ = q * scale`` on read.  The
scales live in a small fp32 sidecar pool (``(num_pages, n_kv_heads)``
per K and V pool — see :func:`pool_spec`), and dequantization happens
*inside* the paged-attention kernel, so the dense bf16 view of the cache
is never materialized.

Off-TPU the fp8 formats are **emulated in bf16**: every fp8 value is
exactly representable in bf16 (3- or 4-bit mantissa into bf16's 7, 3- or
4-bit exponent range inside bf16's 8), so rounding through the fp8 dtype
and storing the result in a bf16 pool is bit-identical in value to native
fp8 storage — the numerics are the TPU numerics, only the HBM bytes
differ (which is why the benchmark's HBM accounting uses
:attr:`KVFormat.itemsize`, not the emulation dtype's).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Union

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class KVFormat:
    """One KV-cache storage format (hashable; jit-static).

    ``name`` is the canonical spelling (what ``Policy.parse`` normalizes
    ``kv=`` values to); ``kind`` is ``"none"`` (bf16 passthrough),
    ``"int"`` or ``"float"``; ``fmax`` the largest representable
    magnitude on the format's value grid; ``grid_dtype`` the dtype whose
    value grid quantization rounds to; ``itemsize`` the HBM bytes per
    element in *native* storage (1 for int8/fp8 — the quantity the
    serving trajectory tracks, independent of off-TPU emulation).
    """
    name: str
    kind: str
    fmax: float
    grid_dtype: Any
    itemsize: int

    @property
    def quantized(self) -> bool:
        return self.kind != "none"

    def storage_dtype(self, backend: str = None):
        """The dtype of the page pool arrays.

        int8 stores natively everywhere.  fp8 stores natively on TPU and
        as exact bf16 emulation elsewhere (fp8 values are a subset of
        bf16, so emulation is value-identical — see module docstring).
        """
        if self.kind == "none":
            return jnp.bfloat16
        if self.kind == "int":
            return jnp.int8
        if backend is None:
            backend = jax.default_backend()
        return self.grid_dtype if backend == "tpu" else jnp.bfloat16

    def __str__(self) -> str:
        return self.name


#: bf16 passthrough — the PR-1..4 serving layout, no scales.
BF16 = KVFormat("bf16", "none", 0.0, jnp.bfloat16, 2)
#: symmetric int8, per-page/per-head amax scales.
I8 = KVFormat("i8", "int", 127.0, jnp.int8, 1)
#: fp8 e4m3 (finite-only fn variant): max 448, 3-bit mantissa.
F8_E4M3 = KVFormat("f8_e4m3", "float", 448.0, jnp.float8_e4m3fn, 1)
#: fp8 e3m4: max 15.5, 4-bit mantissa — finer grid, narrower range.
F8_E3M4 = KVFormat("f8_e3m4", "float", 15.5, jnp.float8_e3m4, 1)

FORMATS = {f.name: f for f in (BF16, I8, F8_E4M3, F8_E3M4)}

_ALIASES = {
    "bfloat16": "bf16",
    "int8": "i8",
    "fp8": "f8_e4m3",
    "f8": "f8_e4m3",
    "f8e4m3": "f8_e4m3",
    "e4m3": "f8_e4m3",
    "f8e3m4": "f8_e3m4",
    "e3m4": "f8_e3m4",
}


def resolve(fmt: Union[str, KVFormat, None]) -> KVFormat:
    """A :class:`KVFormat` from a name/alias (``None`` -> bf16)."""
    if fmt is None:
        return BF16
    if isinstance(fmt, KVFormat):
        return fmt
    key = str(fmt).strip().lower()
    key = _ALIASES.get(key, key)
    if key not in FORMATS:
        raise ValueError(
            f"unknown KV format {fmt!r}; known: "
            f"{sorted(FORMATS) + sorted(_ALIASES)}")
    return FORMATS[key]


#: formats the paged-attention kernel does not compile for on TPU: Mosaic
#: has no vector load for float8_e3m4 ("Invalid vector type for load",
#: compiling for v5e).  ``ServeEngine`` refuses them there up front.
TPU_UNSUPPORTED = frozenset({"f8_e3m4"})


def require_supported(fmt: Union[str, KVFormat, None], device) -> KVFormat:
    """``fmt`` resolved, or ValueError when ``device`` cannot serve it."""
    fmt = resolve(fmt)
    if device.platform == "tpu" and fmt.name in TPU_UNSUPPORTED:
        ok = sorted(set(FORMATS) - TPU_UNSUPPORTED)
        raise ValueError(
            f"KV format {fmt.name!r} is not supported on {device.device_kind}"
            f": the paged-attention kernel does not compile for it there; "
            f"use one of {ok}")
    return fmt


def canonical_name(fmt: Union[str, KVFormat, None]) -> str:
    """Canonical format name (what ``Policy.kv_dtype`` stores)."""
    return resolve(fmt).name


def pool_spec(n_pages: int, page_size: int, n_kv_heads: int, head_dim: int,
              fmt: Union[str, KVFormat], dtype=jnp.bfloat16) -> dict:
    """Abstract paged K/V pool container for one attention layer.

    Pools are head-major, ``(n_pages, n_kv_heads, page_size, head_dim)``
    (see :mod:`repro.nn.attention`).  bf16 passthrough: ``{"k", "v"}``
    pools of ``dtype``.  Quantized formats add the fp32 scale sidecar:
    ``{"k", "v", "k_scale", "v_scale"}`` with the pools in the format's
    storage dtype and ``(n_pages, n_kv_heads)`` scales (one amax scale
    per page per kv head — K rows of one page share a head's scale, so
    the sidecar is ~``page_size * head_dim * itemsize / 4`` times smaller
    than the pool it describes).
    """
    fmt = resolve(fmt)
    shape = (n_pages, n_kv_heads, page_size, head_dim)
    if not fmt.quantized:
        return {"k": jax.ShapeDtypeStruct(shape, dtype),
                "v": jax.ShapeDtypeStruct(shape, dtype)}
    sdt = fmt.storage_dtype()
    sc = jax.ShapeDtypeStruct((n_pages, n_kv_heads), jnp.float32)
    return {"k": jax.ShapeDtypeStruct(shape, sdt),
            "v": jax.ShapeDtypeStruct(shape, sdt),
            "k_scale": sc, "v_scale": sc}
