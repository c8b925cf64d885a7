"""repro.quant — sub-bf16 quantized KV-cache storage for serving.

The MPX policy machinery, applied to the inference side: the KV cache's
storage precision becomes a policy component (``Policy.parse("p=f32,
c=bf16,o=bf16,kv=i8")``, ``ServeEngine(kv_dtype="i8")``) instead of a
bf16 constant baked into the page pools.  Decode is HBM-bound on KV page
reads (the paged-attention kernel already streams only allocated pages);
storing pages in int8 or fp8 with per-page/per-head amax scales halves
the remaining bytes per cached token, and the scales ride in a tiny fp32
sidecar pool that the kernel multiplies back onto K/V blocks *in VMEM* —
the dense bf16 view of the cache is never materialized.

- :mod:`~repro.quant.formats`   — :class:`KVFormat` registry (``bf16``
  passthrough, ``i8``, ``f8_e4m3``, ``f8_e3m4``; fp8 emulated exactly in
  bf16 off-TPU) and the pool+sidecar container layout (:func:`pool_spec`)
- :mod:`~repro.quant.ops`       — write-quantize (:func:`quantized_paged_write`:
  gather the touched pages, splice, fresh amax, requantize) and the one
  dequant rule (:func:`dequantize`) shared by kernel and oracle
- :mod:`~repro.quant.reference` — loop-based reference numerics the
  vectorized ops are tested against
"""
from repro.quant.formats import (BF16, F8_E3M4, F8_E4M3, FORMATS, I8,
                                 TPU_UNSUPPORTED, KVFormat, canonical_name,
                                 pool_spec, require_supported, resolve)
from repro.quant.ops import (amax_scale, dequantize, max_write_pages,
                             quantize, quantized_paged_write,
                             quantized_pool_write)

__all__ = [
    "BF16",
    "F8_E3M4",
    "F8_E4M3",
    "FORMATS",
    "I8",
    "KVFormat",
    "TPU_UNSUPPORTED",
    "amax_scale",
    "canonical_name",
    "dequantize",
    "max_write_pages",
    "pool_spec",
    "quantize",
    "quantized_paged_write",
    "quantized_pool_write",
    "require_supported",
    "resolve",
]
