"""Native paged-attention Pallas kernel: page-table-indexed KV streaming,
with in-kernel dequantization of sub-bf16 (int8 / fp8) page pools.

The serving hot path is HBM-bandwidth-bound, and the paged KV layout
(``repro.serve.cache``) stores every slot's cache as fixed-size pages of a
shared head-major ``(P, K, page_size, D)`` pool addressed through a
per-slot page table.  The gather-based path first materializes each slot's *entire
padded* prefix — ``(B, Pmax*page_size, K, D)`` — as a dense copy, per
layer, per tick, garbage sentinel pages included.  This kernel instead
walks each slot's page table directly: the page table and per-slot chunk
``start``/``valid`` counts are scalar-prefetch (SMEM) operands, and the
K/V block index maps resolve logical page ``i`` -> physical page
``table[b, i]`` in the pool, so the DMA engine streams exactly the pages
the scheduler allocated, exactly once.  Head-major pools make each K/V
block one (page, kv head) ``(page_size, D)`` tile whose minor two dims
are the pool's own: the TPU compiler refuses a token-major
``(P, page_size, K, D)`` pool, whose block would squeeze the kv-head axis
into the second-minor position.  Pages past a slot's length
re-issue the previous block index (the pipeline elides the refetch) and
their compute is predicated off — unallocated pages are never read.

Quantized pools (``repro.quant``) add two more VMEM operands: the
``(P, K)`` fp32 amax-scale sidecars for K and V, each handed to the
kernel as one resident ``(1, P)`` row per kv head (a ``(1, 1)`` block
per page is refused by the TPU compiler).  The row's block index changes
only with the grid row, so it is fetched once per (slot, kv head); each
sub-page's scale is selected from it in VMEM by the SAME logical page ->
physical page rule as that sub-page's value block (one shared
``_phys_page`` helper, so a value and its scale can never point at
different pages).  Each K/V block is dequantized *in VMEM* —
``block.astype(f32) * scale``, cast to the query dtype — before the
score/output matmuls.  The pool is streamed at 1 byte/element and the
dense bf16 view of the cache never exists anywhere, neither in HBM nor as
a pool-shaped intermediate.  The sidecars stay out of scalar-prefetch
SMEM deliberately: SMEM is small and the sidecar grows with the *pool*
(``P * K`` fp32 each) — only the O(B * Pmax) page table and the (B,)
start/valid vectors belong there.  The VMEM rows cost ``4 * P`` bytes
each (padded to 8 sublanes), and selecting a scale is an O(P) masked
sum per sub-page.

Queries cover every ``serve_forward`` step shape, not just single-token
decode: q is ``(B, C, H, D)`` where ``C = 1`` is decode and ``C > 1`` a
chunked-prefill, speculative-window, or mixed step, causal by absolute
position (``start[b] + ci``).  GQA keeps the whole query group resident:
the kernel block is ``(C*G, D)`` with ``G = H / K``, one grid row per
(slot, kv-head).  Softmax runs as the usual streaming (m, l, acc)
recurrence in fp32 VMEM scratch; padding chunk positions
(``ci >= valid[b]``) and idle slots (``valid = 0``) output exact zeros.

``pages_per_block`` widens the K-block: each grid step concatenates that
many *logical* pages (each resolved to its own physical page by its own
index map — pages are not physically contiguous, so one block per page is
DMA'd and they meet in VMEM) into a single ``(ppb * page_size, D)``
operand for the score matmul.  With page_size 16 a single page underfills
the MXU's 128-lane contraction dim; ``pages_per_block = 8`` fills it.
Each sub-page block is dequantized with its *own* page's scale before the
concatenation.

Grid: ``(B*K, ceil(Pmax / pages_per_block))`` — logical page blocks
innermost so the fp32 state is carried across one slot's pages, then
reset (``i == 0``) for the next row.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _phys_page(table_ref, start_ref, valid_ref, b, logical, *,
               page_size: int, n_pages: int):
    """Logical page of slot ``b`` -> clamped physical pool page.

    THE logical->physical rule, shared by the K/V value block index maps
    and the kernel body's scale selection (a value and its scale must
    always resolve to the same page): pages past the slot's last used page
    re-issue the last used index, the sentinel is clamped into range —
    compute for either case is predicated off by the kernel body.
    """
    n_pg = pl.cdiv(start_ref[b] + valid_ref[b], page_size)
    i_eff = jnp.minimum(logical, jnp.maximum(n_pg - 1, 0))
    return jnp.minimum(table_ref[b, i_eff], n_pages - 1)


def _paged_kernel(table_ref, start_ref, valid_ref, *refs,
                  page_size: int, scale: float, n_kv: int, group: int,
                  ppb: int, quantized: bool):
    q_ref = refs[0]
    k_refs = refs[1:1 + ppb]
    v_refs = refs[1 + ppb:1 + 2 * ppb]
    refs = refs[1 + 2 * ppb:]
    if quantized:
        scale_refs = refs[:2]                 # (1, P) this kv head's scales
        refs = refs[2:]
    o_ref = refs[0]
    m_scr, l_scr, acc_scr = refs[1:]
    i = pl.program_id(1)
    n_i = pl.num_programs(1)
    b = pl.program_id(0) // n_kv

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = start_ref[b]
    length = start + valid_ref[b]        # cached tokens incl. this chunk
    block_lo = i * ppb * page_size

    def _block(refs_j, scale_ref, j):
        """Sub-page ``j``'s (page_size, D) block, dequantized in VMEM with
        its own page's scale, picked out of the resident scale row by the
        same logical->physical rule as the block's index map."""
        blk = refs_j[...]
        if not quantized:
            return blk
        phys = _phys_page(table_ref, start_ref, valid_ref, b, i * ppb + j,
                          page_size=page_size, n_pages=scale_ref.shape[1])
        row = scale_ref[...]                                    # (1, P)
        hit = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == phys
        sc = jnp.sum(jnp.where(hit, row, 0.0), axis=1, keepdims=True)
        return (blk.astype(jnp.float32) * sc).astype(q_ref.dtype)

    @pl.when(block_lo < length)
    def _body():
        q = q_ref[...]                                    # (C*G, D) bf16
        ks_ref, vs_ref = scale_refs if quantized else (None, None)
        if ppb == 1:
            k = _block(k_refs[0], ks_ref, 0)
            v = _block(v_refs[0], vs_ref, 0)
        else:
            # ppb logical pages, each DMA'd from its own physical page,
            # dequantized with its own scale, concatenated in VMEM into
            # one (ppb*ps, D) matmul operand
            k = jnp.concatenate(
                [_block(r, ks_ref, j) for j, r in enumerate(k_refs)], axis=0)
            v = jnp.concatenate(
                [_block(r, vs_ref, j) for j, r in enumerate(v_refs)], axis=0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (C*G, ppb*ps) f32
        # key absolute position, query chunk index: causal by position,
        # padding queries (ci >= valid) fully masked -> exact-zero rows.
        # Sub-pages past the slot's length (their index map re-issued an
        # allocated page) land at kpos >= length > start + ci: masked.
        kpos = block_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ci = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        ok = (kpos <= start + ci) & (ci < valid_ref[b])
        s = jnp.where(ok, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # masked entries contribute exactly 0 (not exp(NEG_INF - NEG_INF)
        # = 1 on all-masked padding rows), so l stays 0 there and the
        # final divide yields zeros instead of garbage-page averages
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (C*G, D)
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = m_new

    @pl.when(i == n_i - 1)
    def _fin():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, page_table, start, valid, *,
                    k_scales=None, v_scales=None,
                    pages_per_block: int = 1, interpret: bool = False):
    """Paged attention over a shared KV page pool, no gathered copy.

    q (B, C, H, D) — one serving chunk per slot (C = 1 decode, C > 1
    prefill / speculative window / mixed); k_pages / v_pages
    (P, K, page_size, D) — the shared head-major pools, chunk K/V already
    scattered in (``paged_write`` / ``quantized_pool_write`` runs first);
    page_table (B, Pmax) int32 logical->physical map whose unallocated
    entries hold the sentinel ``P``; start (B,) absolute position of
    each slot's chunk; valid (B,) real tokens in the chunk (0 = idle
    slot).

    ``k_scales`` / ``v_scales`` (P, K) fp32 enable the quantized path:
    the pools hold int8 or fp8 (``repro.quant`` formats) and every K/V
    block is dequantized in VMEM — ``block * scales[phys, kv_head]`` —
    before its matmul.  Both must be given together; without them the
    pools are attended to as-is (the bf16 baseline).  Each sidecar
    reaches the kernel as a resident (1, P) VMEM row per kv head, and
    each sub-page's scale is selected from it by the same
    logical->physical rule as the sub-page's values.

    Query ``ci`` of slot ``b`` attends causally to cache positions
    ``<= start[b] + ci``; padding positions (``ci >= valid[b]``) and idle
    slots output zeros.  ``pages_per_block`` logical pages are fused into
    each K-block (score-matmul contraction dim ``pages_per_block *
    page_size`` — fill it to ~128 lanes on the MXU).  Returns
    (B, C, H, D) in q.dtype.  K divides H; sliding windows and logit
    softcaps are the caller's fallback path.
    """
    b, c, h, d = q.shape
    n_pages, kv, page_size, _ = k_pages.shape
    if h % kv:
        raise ValueError(f"n_kv_heads {kv} must divide n_heads {h}")
    if pages_per_block < 1:
        raise ValueError(f"pages_per_block must be >= 1: {pages_per_block}")
    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be given together")
    quantized = k_scales is not None
    group = h // kv
    cg = c * group
    scale = 1.0 / math.sqrt(d)
    pmax = page_table.shape[1]
    ppb = min(pages_per_block, pmax)

    # (B, C, H, D) -> one (C*G, D) query block per (slot, kv-head) row
    qf = (q.reshape(b, c, kv, group, d).transpose(0, 2, 1, 3, 4)
          .reshape(b * kv, cg, d))
    table = jnp.asarray(page_table, jnp.int32)
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (b,))
    valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32).reshape(-1), (b,))

    def sub_page_phys(bk, i, j, table_ref, start_ref, valid_ref):
        # logical page i*ppb + j of slot bk//kv -> physical pool page,
        # via the ONE shared rule (_phys_page).  Blocks past the slot's
        # last used page re-issue the last used index (no refetch,
        # compute predicated off); the sentinel (= n_pages) only
        # survives for idle slots, clamped into range with compute
        # predicated off.
        return _phys_page(table_ref, start_ref, valid_ref, bk // kv,
                          i * ppb + j, page_size=page_size,
                          n_pages=n_pages)

    def page_index(j):
        def index_map(bk, i, *scalar_refs):
            phys = sub_page_phys(bk, i, j, *scalar_refs)
            return (phys, bk % kv, 0, 0)
        return index_map

    # head-major pools: one (page_size, D) block per (page, kv head).  Its
    # minor two dims are the array's own, so the TPU tiling rules accept
    # it (compiled for v5e at page_size 16: tests/test_tpu_compile.py)
    kv_specs = [pl.BlockSpec((None, None, page_size, d), page_index(j))
                for j in range(ppb)]
    inputs = [qf] + [k_pages] * ppb + [v_pages] * ppb
    in_specs = ([pl.BlockSpec((None, cg, d), lambda bk, i, *_: (bk, 0, 0))]
                + kv_specs + kv_specs)
    if quantized:
        # (P, K) sidecar -> one resident (1, P) row per kv head; its block
        # index only changes with the grid row, so it is fetched once per
        # (slot, kv head) and each sub-page's scale is selected in VMEM
        sc_spec = pl.BlockSpec((None, 1, n_pages),
                               lambda bk, i, *_: (bk % kv, 0, 0))
        inputs += [jnp.asarray(sc, jnp.float32).T.reshape(kv, 1, n_pages)
                   for sc in (k_scales, v_scales)]
        in_specs += [sc_spec, sc_spec]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b * kv, -(-pmax // ppb)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, cg, d), lambda bk, i, *_: (bk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((cg, 1), jnp.float32),
            pltpu.VMEM((cg, 1), jnp.float32),
            pltpu.VMEM((cg, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size, scale=scale,
                          n_kv=kv, group=group, ppb=ppb,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kv, cg, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(table, start, valid, *inputs)
    return (out.reshape(b, kv, c, group, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, c, h, d))


def paged_attention_ref(q, k_pages, v_pages, page_table, start, valid):
    """Ragged pure-jnp paged oracle: see :func:`repro.kernels.ref`."""
    from repro.kernels import ref
    return ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                   start, valid)
