"""Per-layer-kind paged state pool: paged KV for attention layers,
O(1) per-slot state for recurrent layers, one host allocator for both —
with refcounted, copy-on-write page sharing for cached prefixes.

Every layer kind gets the state layout its decode math wants:

- **attention** ('attn', 'local_attn') — the paged KV pool.  The
  monolithic ``T.init_cache`` slab commits ``n_slots * max_seq`` of KV
  HBM up front whether slots are busy or not; the paged pool commits
  memory per *admitted request* instead: a shared pool of ``num_pages``
  fixed-size pages per attention layer, and a page table row per slot
  mapping logical page -> physical page.  Token position ``p`` of slot
  ``b``, kv head ``h`` lives at
  ``pages[table[b, p // page_size], h, p % page_size]`` (head-major
  pools, the layout the paged-attention kernel compiles for on TPU).
- **recurrent** ('rglru', 'ssd') — O(1) per-slot decode state (the
  RG-LRU hidden vector + conv buffer, the SSD state accumulator + conv
  buffers), batch row = slot.  No pages, no page-table entries, no
  reservation pressure on the pool: the state neither grows with
  sequence length nor fragments, so the allocator's only job is hygiene
  — **admitting a slot zeroes its recurrent state rows** (a jitted
  donated ``.at[slot].set(0)``, dispatched asynchronously; no host
  sync) so a reused slot can never leak the previous request's state.
  Per the MPX fragile-spot policy the carried states are fp32 (the
  recurrences compound rounding over thousands of steps); conv buffers
  ride the compute dtype.

A pure-recurrent config gets ``num_pages = 0`` — no KV pools exist and
admission never touches the free list.  Hybrid stacks use both halves at
once: attention layers reserve pages, recurrent layers reset their rows,
one ``admit()`` call.

**Storage precision is a policy, not a constant** (``kv_dtype``, a
``repro.quant`` format).  The bf16 passthrough is one
``(num_pages, K, page_size, D)`` bf16 K and V pool per attention layer.
Quantized formats ("i8", "f8_e4m3", "f8_e3m4") store the pools at
1 byte/element on the format's value grid and add a
``(num_pages, K)`` fp32 amax-scale *sidecar* per pool — one symmetric
scale per (page, kv-head), ~``page_size * head_dim / 4`` times smaller
than the pool it describes.  The write-quantize / read-dequantize
contract:

- **writes quantize** — ``paged_attend`` routes each chunk's new K/V
  through :func:`repro.quant.ops.quantized_pool_write`, which gathers
  exactly the pages the chunk touches, splices the new values into
  their dequantized image, recomputes each touched page's amax, and
  requantizes that page (untouched pages keep their bits and scales);
- **reads dequantize in the consumer** — the paged-attention kernel
  multiplies the sidecar scales back onto K/V blocks in VMEM before the
  score/output matmuls (the gather fallback dequantizes its dense
  oracle view), so the sub-bf16 pool is the only HBM-resident image of
  the cache and decode's KV read traffic drops with the itemsize.

**Prefix caching** (``prefix_cache=True``) adds page-level sharing on
top: every page carries a **refcount**, and a content-addressed *prefix
index* maps a chained per-page hash of committed token ids to the
physical page already holding that page's KV.  When a new request's
feed begins with pages that are resident — a hot system prompt, a
few-shot template, a preempted request re-admitting its own history —
admission maps the slot's page table onto those pages (refcount
incremented, zero device work) and chunked prefill **skips the cached
tokens entirely**: the paged-attention kernel needs no changes because
it already resolves logical -> physical pages through the per-slot
table.  A retiring slot *decrements* instead of freeing; a registered
page whose refcount reaches zero parks on an LRU list of **cached**
pages — still resident, still hittable, reclaimed lazily (LRU-first)
when the allocator runs out of free pages, and always reclaimed before
a live slot would be preempted.  Writes into a shared page never happen
in place: the one geometric case where a new tenant must write into a
hit page (every feed page hit, so the final feed token — at least one
token must be fed to produce logits — lands in the last shared page)
is resolved by **copy-on-write at admission**: a private physical copy
is queued (value pages AND the fp32 amax-scale sidecars — requantizing
scatter is a read-modify-write of the whole touched page, so it must
never see another tenant's page), the slot's table points at the copy,
and :meth:`flush_cow` dispatches all pending copies in one donated
jitted gather/scatter right before the engine's device step.
``note_write`` re-checks the planned write span and COWs defensively if
any target page is still shared — the write paths
(:func:`repro.nn.attention.paged_write`,
:func:`repro.quant.ops.quantized_paged_write`) therefore always own
their touched pages exclusively.  Sharing by token *ids* is only sound
when skipping prefill is: recurrent layers carry history-dependent
per-slot state that cannot be skipped into existence, so
``prefix_cache`` is active only for pure-attention stacks (the flag is
accepted and ignored, with all refcounts pinned at <= 1, otherwise).

Bookkeeping (free list, tables, refcounts, per-slot lengths, the prefix
index) is host-side numpy/dict — it mutates a few ints per request, never
touches the device, and stays out of the jitted step.  Passing a
``repro.obs`` registry makes the allocator observable at the same zero
device cost: ``serve_pages_free`` / ``serve_pages_used`` /
``serve_pages_used_peak`` gauges (the peak is the pool-sizing signal),
``serve_pages_shared`` / ``serve_pages_cached`` gauges for the sharing
layer, ``serve_prefix_hits_total`` / ``serve_prefix_miss_total`` /
``serve_cow_copies_total`` counters for the prefix index, and
``serve_truncations_total`` / ``serve_spec_rejected_tokens_total``
counters for speculative tails discarded by ``truncate()``.  The device
side is a pytree of page pools (scale sidecars riding in the same
per-layer dicts, scan-stacked like the params) built by
:func:`repro.models.transformer.init_paged_cache`; all layers share one
table, so admission allocates pages once per sequence.

Allocation policy: the full budget (prompt + max_new tokens) is reserved
at admission, so a running request can never hit pool exhaustion
mid-decode — admission control is the only backpressure point.  (A
shared prefix page counts against the reservation exactly once per
tenant: refcounts make the accounting per-reference, not per-page.)
Speculative decoding adds a second, token-granular piece of bookkeeping
on top: a step may *write* KV for a whole proposed window
(``note_write``) and then *commit* only the accepted prefix
(``truncate``), leaving the rejected tail as dead positions beyond the
slot's length.  No page churn happens — the pages were reserved at
admission and the dead positions are overwritten by the next window —
but the committed/written watermarks make the invariant ("committed <=
written <= reserved capacity, never rolling a committed prefix back")
explicitly checkable.  Only *full, committed* pages register in the
prefix index, and a slot's forward writes always begin at its committed
length, so a registered page is immutable for as long as it is resident
— rollback can land in a COW copy, never in the original.  (Under a
quantized ``kv_dtype`` a dead tail can still nudge a page's amax until
it is overwritten — it costs precision headroom, never correctness,
since attention masks by committed position.)  Recurrent state only
moves forward — there is no watermark to truncate back to — so
speculative windows are refused at engine construction for
recurrent/hybrid stacks (see :class:`~repro.serve.engine.ServeEngine`).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as tfm
from repro.quant import formats as qfmt

PyTree = Any

#: bytes per chained page digest (blake2b) — 128 bits: collisions across
#: a pool of at most a few thousand resident pages are not a concern.
_DIGEST_BYTES = 16


class PagedKVCache:
    """Per-layer-kind state pool + host allocator for ``n_slots`` slots.

    Attention layers get device page pools; recurrent layers get
    slot-indexed state rows (reset on admit).  The sentinel physical index
    ``num_pages`` marks unallocated table entries: device-side writes
    through it are dropped, reads are clamped and masked by sequence
    length.  ``kv_dtype`` selects the KV page storage format
    (``repro.quant`` name or :class:`~repro.quant.KVFormat`;
    "bf16" = passthrough, quantized formats add the scale sidecars) —
    recurrent state precision is policy-pinned (fp32 carried state),
    not configurable here.  ``prefix_cache=True`` enables refcounted
    prefix-page sharing with copy-on-write (pure-attention stacks only;
    see the module docstring) — with it off, every page's refcount stays
    <= 1 and the allocator behaves exactly as before.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, max_seq: int, *,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 dtype=jnp.bfloat16,
                 kv_dtype: Union[str, qfmt.KVFormat] = "bf16",
                 prefix_cache: bool = False,
                 registry=None):
        if max_seq % page_size:
            raise ValueError(f"max_seq {max_seq} must be a multiple of "
                             f"page_size {page_size}")
        self.cfg = cfg
        self.max_seq = max_seq
        kinds = cfg.layer_kinds()
        self.has_paged = any(k in ("attn", "local_attn") for k in kinds)
        self.has_recurrent = any(k in tfm._RECURRENT_KINDS for k in kinds)
        self.page_size = page_size
        self.max_pages_per_slot = max_seq // page_size
        if not self.has_paged:
            num_pages = 0            # page-free stack: no KV pools at all
        self.num_pages = (num_pages if num_pages is not None
                          else n_slots * self.max_pages_per_slot)
        self.n_slots = n_slots
        self.sentinel = self.num_pages
        self.kv_format = qfmt.resolve(kv_dtype)
        # prefix sharing needs pages to share AND the license to skip
        # prefill over them; recurrent state is a function of the full
        # token history, so a skipped prefix would leave it wrong —
        # accept the flag but keep sharing inert for those stacks.
        self.prefix_cache = bool(prefix_cache and self.has_paged
                                 and not self.has_recurrent)
        self.pages: PyTree = tfm.init_paged_cache(
            cfg, self.num_pages, page_size, dtype,
            kv_format=self.kv_format.name, n_slots=n_slots)
        # slot admission state: recurrent rows have no pages to witness
        # occupancy, so track it explicitly.  ``_dirty`` marks slots whose
        # recurrent state still holds a retired request's values; admit()
        # must clear it by resetting the rows before reuse
        # (check_invariants catches stale-state leaks).
        self._admitted: List[bool] = [False] * n_slots
        self._reserved: List[int] = [0] * n_slots
        self._dirty: List[bool] = [False] * n_slots
        self._reset_slot_state = None
        if self.has_recurrent:
            mask = tfm.slot_state_mask(cfg, kv_format=self.kv_format.name)

            def raw_reset(pages, slot):
                out = {}
                for key, sub in pages.items():
                    stacked = key == "scan"
                    out[key] = jax.tree.map(
                        lambda a, m, st=stacked: (
                            (a.at[:, slot].set(jnp.zeros((), a.dtype))
                             if st else
                             a.at[slot].set(jnp.zeros((), a.dtype)))
                            if m else a),
                        sub, mask[key])
                return out

            self._reset_slot_state = jax.jit(raw_reset, donate_argnums=(0,))
        self._free: List[int] = list(range(self.num_pages))
        # fault-injection hold (see hold_pages): pages taken out of the
        # free list without an owner.  A first-class page state —
        # check_invariants accounts for it, so a scripted exhaustion
        # window can't masquerade as a leak.
        self._held: List[int] = []
        # page-sharing state.  Every physical page is in exactly one of
        # four states, which check_invariants proves cover the pool:
        #   free       — on ``_free``, refcount 0, unregistered
        #   held       — on ``_held`` (fault injection), refcount 0
        #   referenced — refcount >= 1: mapped by that many slot tables
        #   cached     — refcount 0 but registered in the prefix index;
        #                parked on the ``_lru`` list (oldest first),
        #                evicted lazily under allocation pressure
        self._refcount: List[int] = [0] * self.num_pages
        self._index: Dict[bytes, int] = {}       # chained digest -> phys
        self._page_digest: Dict[int, bytes] = {}  # phys -> chained digest
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        # rolling per-page hash state per slot: (pages hashed so far,
        # chain digest after them).  Extended incrementally as chunks
        # commit, so registration and the admission probe never rehash
        # an already-hashed prefix — O(pages touched), not O(context).
        self._hash_seed = hashlib.blake2b(
            f"{cfg.name}:{self.kv_format.name}:{page_size}".encode(),
            digest_size=_DIGEST_BYTES).digest()
        self._hash_state: List[Tuple[int, bytes]] = [
            (0, self._hash_seed)] * n_slots
        # queued copy-on-write page copies, flushed in one donated jitted
        # gather/scatter (values + scale sidecars) before the device step
        self._cow_pending: List[Tuple[int, int]] = []
        self._copy_pages = None
        if self.prefix_cache:
            mask = tfm.slot_state_mask(cfg, kv_format=self.kv_format.name)

            def raw_copy(pages, src, dst):
                out = {}
                for key, sub in pages.items():
                    stacked = key == "scan"
                    out[key] = jax.tree.map(
                        lambda a, m, st=stacked: a if m else (
                            a.at[:, dst].set(a[:, src]) if st
                            else a.at[dst].set(a[src])),
                        sub, mask[key])
                return out

            self._copy_pages = jax.jit(raw_copy, donate_argnums=(0,))
        self._tables = np.full((n_slots, self.max_pages_per_slot),
                               self.sentinel, np.int32)
        self._owned: List[List[int]] = [[] for _ in range(n_slots)]
        # per-slot token watermarks: committed <= written <= capacity.
        # ``written`` is the KV high-water mark (speculative windows write
        # ahead of the committed length); ``committed`` the accepted prefix.
        self._committed: List[int] = [0] * n_slots
        self._written: List[int] = [0] * n_slots
        self._table_device = None        # invalidated on alloc/free
        # telemetry (repro.obs): page-pool occupancy gauges + a
        # high-watermark, prefix-index hit/miss/COW counters, and the
        # speculative rejected-tail counter.  All host-side ints — the
        # allocator never touches the device, so neither does its
        # instrumentation.  None = uninstrumented.
        self._free_gauge = self._used_gauge = self._peak_gauge = None
        self._shared_gauge = self._cached_gauge = None
        self._truncations = self._rejected_tokens = None
        self._hits = self._misses = self._cows = None
        if registry is not None:
            state_bytes = registry.gauge(
                "serve_state_bytes",
                "decode-state bytes held per layer kind "
                "(KV page pools vs O(1) recurrent slot state)",
                labels=("kind",))
            for kind, nbytes in self._state_bytes_by_kind().items():
                state_bytes.set(nbytes, kind=kind)
            self._free_gauge = registry.gauge(
                "serve_pages_free", "free pages in the shared pool")
            self._used_gauge = registry.gauge(
                "serve_pages_used", "pages held by admitted slots")
            self._peak_gauge = registry.gauge(
                "serve_pages_used_peak",
                "high-watermark of pages held (pool sizing signal)")
            self._shared_gauge = registry.gauge(
                "serve_pages_shared",
                "physical pages mapped by more than one slot (refcount "
                ">= 2)")
            self._cached_gauge = registry.gauge(
                "serve_pages_cached",
                "unreferenced pages parked in the prefix index "
                "(LRU-evictable under pool pressure)")
            self._truncations = registry.counter(
                "serve_truncations_total",
                "truncate() calls that discarded written positions")
            self._rejected_tokens = registry.counter(
                "serve_spec_rejected_tokens_total",
                "speculative window positions rolled back by truncate()")
            self._hits = registry.counter(
                "serve_prefix_hits_total",
                "feed pages mapped onto resident cached pages at "
                "admission")
            self._misses = registry.counter(
                "serve_prefix_miss_total",
                "admission probes that ended on an uncached feed page")
            self._cows = registry.counter(
                "serve_cow_copies_total",
                "shared pages privately copied before a divergent write")
            self._free_gauge.set(self.num_pages)
            self._used_gauge.set(0)
            self._peak_gauge.set(0)
            self._shared_gauge.set(0)
            self._cached_gauge.set(0)
            # export the counters from tick zero (schema-pinned by
            # tests/test_obs.py): inc(0) materializes the series
            self._hits.inc(0)
            self._misses.inc(0)
            self._cows.inc(0)

    def _update_pool_gauges(self) -> None:
        if self._free_gauge is not None:
            used = self.used_pages
            self._free_gauge.set(len(self._free))
            self._used_gauge.set(used)
            self._peak_gauge.set_max(used)
            self._shared_gauge.set(self.shared_pages)
            self._cached_gauge.set(len(self._lru))

    def _state_bytes_by_kind(self) -> Dict[str, int]:
        """Device bytes of decode state held per layer kind (where decode
        memory lives: KV page pools vs O(1) recurrent slot state)."""
        n_groups, rem = tfm._layout(self.cfg)
        totals: Dict[str, int] = {}

        def add(kind: str, sub: PyTree) -> None:
            nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                         for a in jax.tree.leaves(sub))
            totals[kind] = totals.get(kind, 0) + nbytes

        if n_groups > 0:
            for i, kind in enumerate(self.cfg.pattern):
                add(kind, self.pages["scan"][f"b{i}"])
        for j, kind in enumerate(rem):
            add(kind, self.pages[f"tail{j}"])
        return totals

    # -- refcounting / page states ------------------------------------------

    def _incref(self, page: int) -> None:
        if self._refcount[page] == 0:
            self._lru.pop(page, None)    # cached -> referenced
        self._refcount[page] += 1

    def _decref(self, page: int) -> None:
        rc = self._refcount[page] = self._refcount[page] - 1
        if rc < 0:
            raise RuntimeError(f"page {page}: refcount underflow")
        if rc == 0:
            if page in self._page_digest:
                self._lru[page] = None   # referenced -> cached (MRU end)
            else:
                self._free.append(page)  # referenced -> free

    def _evict_cached(self) -> int:
        """Reclaim the least-recently-parked cached page: drop it from
        the prefix index and return it (now free for reuse)."""
        page, _ = self._lru.popitem(last=False)
        digest = self._page_digest.pop(page)
        del self._index[digest]
        return page

    def _alloc_page(self) -> int:
        """One unreferenced physical page: the free list first, then LRU
        eviction of cached pages — cached prefixes are reclaimed lazily,
        and always before admission pressure escalates to preempting a
        live slot (the scheduler only preempts when this pool, cached
        pages included, cannot cover a reservation)."""
        if self._free:
            return self._free.pop()
        if self._lru:
            return self._evict_cached()
        raise RuntimeError(
            "page pool exhausted: no free and no cached-evictable pages "
            "— admission accounting should have prevented this "
            "allocation")

    def _page_hash(self, prev: bytes, tokens: Sequence[int]) -> bytes:
        """Chained digest of one page's token ids: H(prev || ids).

        Chaining makes a page's digest identify the *entire prefix*
        through it, so matching page k implies pages 0..k-1 matched too —
        the index needs no trie, just a flat digest -> page dict."""
        h = hashlib.blake2b(prev, digest_size=_DIGEST_BYTES)
        h.update(np.asarray(tokens, np.int64).tobytes())
        return h.digest()

    # -- allocation ---------------------------------------------------------

    def pages_for(self, n_tokens: int) -> int:
        """Number of pages a sequence of ``n_tokens`` tokens occupies."""
        return -(-n_tokens // self.page_size)

    def can_admit(self, n_tokens: int) -> bool:
        if not self.has_paged:
            return n_tokens <= self.max_seq
        return self.pages_for(n_tokens) <= self.available_pages

    def admit(self, slot: int, n_tokens: int,
              feed: Optional[Sequence[int]] = None) -> bool:
        """Reserve capacity for ``n_tokens`` total tokens in ``slot``:
        pages for the attention layers (if any), plus a zero-reset of the
        slot's recurrent state rows (if any).

        With the prefix cache enabled, ``feed`` (the token ids chunked
        prefill would write) is probed against the prefix index page by
        page — hashing lazily and stopping at the first miss, so the
        probe costs O(pages hit), not O(context).  Hit pages are mapped
        into the slot's table with their refcount incremented and the
        slot's committed/written watermarks start past them
        (:meth:`slot_length` tells the scheduler how many feed tokens to
        skip).  At least one token must always be fed to produce logits,
        so when *every* feed page hits, the final feed token is re-fed —
        and because that write would land inside the last shared page,
        that page is copy-on-write'd here, at admission (the private
        copy is queued for :meth:`flush_cow`; the reservation accounts
        for the extra page).

        Returns False (allocating nothing) if the pool or the slot's
        table row can't hold the request.
        """
        if self._admitted[slot] or self._owned[slot]:
            raise ValueError(f"slot {slot} already holds pages")
        if n_tokens > self.max_seq:
            return False
        need = self.pages_for(n_tokens) if self.has_paged else 0
        if need > self.max_pages_per_slot:
            return False
        ps = self.page_size
        shared: List[int] = []
        digests: List[bytes] = []
        probe_missed = False
        feed_len = len(feed) if feed is not None else 0
        if self.prefix_cache and feed_len >= ps:
            d = self._hash_seed
            for k in range(feed_len // ps):
                d = self._page_hash(d, feed[k * ps:(k + 1) * ps])
                phys = self._index.get(d)
                if phys is None:
                    probe_missed = True
                    break
                shared.append(phys)
                digests.append(d)
        # the skip cap: at least one feed token must run through the
        # model to produce the logits the first sample needs
        skip = min(len(shared) * ps, feed_len - 1) if shared else 0
        boundary = bool(shared) and len(shared) * ps > skip
        n_mapped = len(shared) - 1 if boundary else len(shared)
        fresh_needed = need - n_mapped
        hit_set = set(shared)
        evictable = sum(1 for p in self._lru if p not in hit_set)
        if fresh_needed > len(self._free) + evictable:
            return False             # clean failure: nothing was mutated
        if self._hits is not None:
            if shared:
                self._hits.inc(len(shared))
            if probe_missed:
                self._misses.inc()
        row = list(shared[:n_mapped])
        for p in row:
            self._incref(p)
        cow_src = shared[-1] if boundary else None
        if cow_src is not None:
            # pin the COW source against this admission's own LRU
            # eviction while the fresh pages are allocated
            self._incref(cow_src)
        fresh = [self._alloc_page() for _ in range(fresh_needed)]
        for p in fresh:
            self._incref(p)
        if cow_src is not None:
            # the boundary page: fresh[0] is its private copy at logical
            # index ``n_mapped`` — queue the device copy (value pages and
            # scale sidecars alike) and unpin the source, which stays
            # resident for other tenants / the index
            self._cow_pending.append((cow_src, fresh[0]))
            if self._cows is not None:
                self._cows.inc()
            self._decref(cow_src)
        row += fresh
        self._owned[slot] = row
        self._tables[slot, :len(row)] = row
        self._admitted[slot] = True
        self._reserved[slot] = n_tokens
        self._committed[slot] = skip
        self._written[slot] = skip
        self._hash_state[slot] = ((len(shared), digests[-1]) if shared
                                  else (0, self._hash_seed))
        self._table_device = None
        if self._reset_slot_state is not None:
            # async jit dispatch — zeroes the slot's recurrent rows on
            # device (donated buffers, no host transfer, no sync)
            self.pages = self._reset_slot_state(self.pages,
                                                jnp.int32(slot))
            self._dirty[slot] = False
        self._update_pool_gauges()
        return True

    def retire(self, slot: int) -> None:
        """Drop the slot's references and mark its recurrent state rows
        stale (the next ``admit`` must reset them).  A page this slot
        shared with another stays referenced; a registered page whose
        last reference this was parks on the cached LRU list (still
        hittable); everything else returns to the free list."""
        if self._admitted[slot] and self.has_recurrent:
            self._dirty[slot] = True
        for p in self._owned[slot]:
            self._decref(p)
        if self._cow_pending:
            # drop queued copies whose destination just lost its only
            # owner — the copy would scribble on a page the allocator
            # may hand to the next admission
            self._cow_pending = [(s, d) for s, d in self._cow_pending
                                 if self._refcount[d] > 0]
        self._owned[slot] = []
        self._tables[slot, :] = self.sentinel
        self._admitted[slot] = False
        self._reserved[slot] = 0
        self._committed[slot] = 0
        self._written[slot] = 0
        self._hash_state[slot] = (0, self._hash_seed)
        self._table_device = None
        self._update_pool_gauges()

    def slot_pages(self, slot: int) -> int:
        """Pages currently owned by ``slot`` (0 when idle or page-free)."""
        return len(self._owned[slot])

    def reclaimable_pages(self, slot: int) -> int:
        """Pages that evicting ``slot`` would make allocatable: its
        exclusively-referenced pages (refcount 1 — they go free or
        cached-evictable on retire).  Shared pages stay referenced by
        their other tenants and are not reclaimed."""
        return sum(1 for p in self._owned[slot] if self._refcount[p] == 1)

    # -- prefix index -------------------------------------------------------

    def note_committed(self, slot: int, ctx: Sequence[int]) -> None:
        """Register the slot's newly *full, committed* pages in the
        prefix index.  ``ctx`` is the slot's token history (prompt +
        committed generations); position ``p`` of the slot's KV holds
        ``ctx[p]`` for every committed position.

        Hashing is incremental: the slot carries (pages hashed, chain
        digest) and only the pages the committed watermark newly crossed
        are hashed — O(new pages), never a rehash of the prefix.  First
        registration wins: a digest already in the index (this slot
        admitted *through* it, or a concurrent slot beat it) is skipped,
        so exactly one physical page is canonical per prefix."""
        if not self.prefix_cache:
            return
        ps = self.page_size
        hashed, d = self._hash_state[slot]
        full = self._committed[slot] // ps
        while hashed < full:
            d = self._page_hash(d, ctx[hashed * ps:(hashed + 1) * ps])
            phys = int(self._tables[slot, hashed])
            if d not in self._index and phys not in self._page_digest:
                self._index[d] = phys
                self._page_digest[phys] = d
            hashed += 1
        self._hash_state[slot] = (hashed, d)

    def _cow_page(self, slot: int, logical: int) -> int:
        """Give ``slot`` a private copy of its shared ``logical`` page
        before a write can touch it: allocate a fresh physical page,
        queue the device copy (value pages and scale sidecars), patch
        the slot's table/ownership, and drop the slot's reference on the
        original — which stays intact for its other tenants."""
        old = int(self._tables[slot, logical])
        new = self._alloc_page()
        self._incref(new)
        self._cow_pending.append((old, new))
        self._tables[slot, logical] = new
        self._owned[slot][logical] = new
        self._decref(old)
        self._table_device = None
        if self._cows is not None:
            self._cows.inc()
        self._update_pool_gauges()
        return new

    def flush_cow(self) -> None:
        """Dispatch every queued copy-on-write page copy as one donated
        jitted gather/scatter over the page-pool leaves (scale sidecars
        included).  The engine calls this after planning and before the
        device step, so a write never races its page's copy.  Async
        dispatch — no host sync."""
        if not self._cow_pending:
            return
        pairs, self._cow_pending = self._cow_pending, []
        src = jnp.asarray(np.array([s for s, _ in pairs], np.int32))
        dst = jnp.asarray(np.array([d for _, d in pairs], np.int32))
        self.pages = self._copy_pages(self.pages, src, dst)

    # -- fault injection (repro.serve.faults) --------------------------------

    def hold_pages(self, n: Optional[int] = None) -> int:
        """Take up to ``n`` pages (all free pages when None) out of the
        free list with no owner — the fault-injection seam that simulates
        pool exhaustion.  Held pages stay fully accounted
        (``check_invariants`` treats held as a first-class page state
        beside owned, free and cached); :meth:`release_held` returns
        them.  Cached pages are not holdable — they carry data and stay
        reclaimable, which is exactly the semantics sharing wants under
        pressure.  Returns the number of pages actually taken."""
        if not self.has_paged:
            return 0
        take = len(self._free) if n is None else min(int(n),
                                                     len(self._free))
        for _ in range(take):
            self._held.append(self._free.pop())
        self._update_pool_gauges()
        return take

    def release_held(self) -> int:
        """Return every held page to the free list; returns the count."""
        n = len(self._held)
        self._free.extend(self._held)
        self._held = []
        self._update_pool_gauges()
        return n

    # -- length bookkeeping (speculative windows) ---------------------------

    def capacity(self, slot: int) -> int:
        """Tokens the slot can hold: its reserved pages for paged stacks,
        the admitted request's token budget for page-free ones."""
        if self.has_paged:
            return len(self._owned[slot]) * self.page_size
        return self._reserved[slot]

    def slot_length(self, slot: int) -> int:
        """The slot's committed token count (accepted prefix).  Right
        after :meth:`admit` this is the cached-prefix skip: the number
        of feed tokens whose KV is already resident via shared pages."""
        return self._committed[slot]

    def note_write(self, slot: int, end: int) -> None:
        """Record that KV for positions ``[0, end)`` has been written.

        The scheduler calls this when it plans a chunk or speculative
        window for the slot; ``end`` may run ahead of the committed length
        by the window size but never past the reserved capacity.  The
        planned span always starts at the current written watermark
        (prefill resumes at ``fed``, decode at the committed length), so
        this is also the copy-on-write barrier: any still-shared page in
        the span gets a private copy *before* the device step's
        ``paged_write`` / ``quantized_paged_write`` can touch it — the
        requantizing scatter is a read-modify-write of whole pages and
        must never see a page another slot maps.
        """
        if end > self.capacity(slot):
            raise RuntimeError(
                f"slot {slot}: write to position {end} exceeds reserved "
                f"capacity {self.capacity(slot)} "
                f"({len(self._owned[slot])} pages x {self.page_size})")
        if self.prefix_cache and end > self._written[slot]:
            ps = self.page_size
            for logical in range(self._written[slot] // ps,
                                 (end - 1) // ps + 1):
                if self._refcount[int(self._tables[slot, logical])] > 1:
                    self._cow_page(slot, logical)
        self._written[slot] = max(self._written[slot], end)

    def truncate(self, slot: int, new_len: int) -> None:
        """Commit the slot's length to ``new_len``, discarding any written
        positions beyond it (rejected speculative tokens).

        The dead tail needs no page churn — pages were reserved at
        admission and the next window overwrites those positions before
        anything can read them (attention masks by position).  Shared
        prefix pages are below the committed watermark by construction
        (only full committed pages register, and rollback never crosses
        ``committed``), so a truncate can land in a COW copy but never
        in a page another slot references.  Raises ``RuntimeError`` if
        ``new_len`` rolls back a committed prefix or claims positions
        that were never written.
        """
        if new_len < self._committed[slot]:
            raise RuntimeError(
                f"slot {slot}: truncate to {new_len} would roll back the "
                f"committed prefix ({self._committed[slot]} tokens)")
        if new_len > self._written[slot]:
            raise RuntimeError(
                f"slot {slot}: truncate to {new_len} beyond written "
                f"watermark {self._written[slot]}")
        rejected = self._written[slot] - new_len
        if rejected and self._truncations is not None:
            self._truncations.inc()
            self._rejected_tokens.inc(rejected)
        self._committed[slot] = new_len
        self._written[slot] = new_len

    # -- views --------------------------------------------------------------

    def table_device(self) -> jnp.ndarray:
        """(n_slots, max_pages_per_slot) int32 page table on device."""
        if self._table_device is None:
            self._table_device = jnp.asarray(self._tables)
        return self._table_device

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def cached_pages(self) -> int:
        """Unreferenced registered pages parked on the LRU list —
        resident and hittable, reclaimed lazily under pressure."""
        return len(self._lru)

    @property
    def available_pages(self) -> int:
        """Pages an admission could obtain: free plus cached-evictable."""
        return len(self._free) + len(self._lru)

    @property
    def shared_pages(self) -> int:
        """Physical pages currently mapped by more than one slot."""
        return sum(1 for rc in self._refcount if rc >= 2)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def held_pages(self) -> int:
        """Pages held out of the pool by fault injection (see
        :meth:`hold_pages`)."""
        return len(self._held)

    @property
    def prefix_hits(self) -> int:
        """Cumulative prefix-cache page hits (0 when uninstrumented) —
        host ints for the flight-recorder tick digest."""
        return int(self._hits.total) if self._hits is not None else 0

    @property
    def prefix_misses(self) -> int:
        """Cumulative prefix-index probe misses (0 when uninstrumented)."""
        return int(self._misses.total) if self._misses is not None else 0

    @property
    def cow_copies(self) -> int:
        """Cumulative copy-on-write page copies (0 when uninstrumented)."""
        return int(self._cows.total) if self._cows is not None else 0

    def check_invariants(self) -> None:
        """Every physical page is in exactly one state — free, held,
        referenced (refcount >= 1), or cached — refcounts equal table
        multiplicity, **no page is simultaneously free and referenced**,
        the prefix index is a bijection onto registered pages, and
        per-slot lengths respect committed <= written <= capacity.

        Raises ``RuntimeError`` (not ``assert`` — these must survive
        ``python -O``) on the first violated invariant.
        """
        counts: Dict[int, int] = {}
        for slot, row in enumerate(self._owned):
            if len(row) != len(set(row)):
                raise RuntimeError(
                    f"slot {slot} maps a physical page twice: {row}")
            for p in row:
                counts[p] = counts.get(p, 0) + 1
        for p in range(self.num_pages):
            if self._refcount[p] != counts.get(p, 0):
                raise RuntimeError(
                    f"page {p}: refcount {self._refcount[p]} but "
                    f"{counts.get(p, 0)} slot(s) map it")
        referenced = set(counts)
        free_set = set(self._free)
        if len(free_set) != len(self._free):
            raise RuntimeError("page on the free list twice")
        if referenced & free_set:
            raise RuntimeError(
                f"page(s) {sorted(referenced & free_set)} simultaneously "
                f"free and referenced")
        held_set = set(self._held)
        cached_set = set(self._lru)
        for name_a, a, name_b, b in (
                ("held", held_set, "free", free_set),
                ("held", held_set, "referenced", referenced),
                ("cached", cached_set, "free", free_set),
                ("cached", cached_set, "referenced", referenced),
                ("cached", cached_set, "held", held_set)):
            if a & b:
                raise RuntimeError(
                    f"page(s) {sorted(a & b)} both {name_a} and {name_b}")
        if (len(referenced) + len(free_set) + len(held_set)
                + len(cached_set) != self.num_pages):
            raise RuntimeError("leaked page")
        if len(self._index) != len(self._page_digest):
            raise RuntimeError("prefix index / digest map out of sync")
        for digest, p in self._index.items():
            if self._page_digest.get(p) != digest:
                raise RuntimeError(
                    f"page {p}: index digest does not round-trip")
        for p in self._page_digest:
            if p in free_set or p in held_set:
                raise RuntimeError(
                    f"registered page {p} is {'free' if p in free_set else 'held'}")
            if counts.get(p, 0) == 0 and p not in cached_set:
                raise RuntimeError(
                    f"registered page {p} neither referenced nor cached")
        for slot, row in enumerate(self._owned):
            mapped = [p for p in self._tables[slot] if p != self.sentinel]
            if mapped != row:
                raise RuntimeError(
                    f"slot {slot}: table/ownership mismatch "
                    f"(mapped {mapped}, owned {row})")
            if not (0 <= self._committed[slot] <= self._written[slot]
                    <= self.capacity(slot)):
                raise RuntimeError(
                    f"slot {slot}: length invariant violated — committed "
                    f"{self._committed[slot]} <= written "
                    f"{self._written[slot]} <= capacity "
                    f"{self.capacity(slot)} must hold")
            if self.has_paged and not row and self._written[slot]:
                raise RuntimeError(
                    f"slot {slot}: nonzero written watermark "
                    f"{self._written[slot]} with no pages owned")
            if self._admitted[slot] and self._dirty[slot]:
                raise RuntimeError(
                    f"slot {slot}: stale recurrent state — the slot was "
                    f"re-admitted without resetting the previous "
                    f"request's device state rows")


# The class predates the per-layer-kind generalization; the name that
# matches what it now is.  Both names are exported from repro.serve.
PagedStatePool = PagedKVCache
