"""Pallas TPU kernels for the hot paths the paper's discipline exposes.

- ``flash_attention``   — blocked prefill attention (fp32 streaming state)
- ``decode_attention``  — one query vs a *contiguous* (B, S, K, D) cache
- ``paged_attention``   — page-table-indexed serving attention over the
  shared head-major (P, K, page_size, D) pools: scalar-prefetch page tables, no
  gathered dense copy, covers decode AND chunked-prefill queries
- ``rmsnorm`` / ``unscale_finite`` — fused MPX precision primitives
- ``ref``               — pure-jnp oracles every kernel is tested against

Every kernel runs under ``interpret=True`` on CPU (that is what tier-1 CI
exercises) and compiles natively on TPU.
"""
