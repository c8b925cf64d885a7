"""The unified transformer: one model, ten architectures.

A :class:`~repro.configs.base.ModelConfig` describes the stack as a repeating
``pattern`` of layer kinds (``attn`` / ``local_attn`` / ``rglru`` / ``ssd``),
each followed by a dense or MoE MLP (or none), and expert-only blocks
(``moe``: a held share of sigmoid-routed experts plus a shared expert,
trained, not served).  Repeated pattern groups are
parameter-stacked and driven by ``jax.lax.scan`` — the single most important
compile-time lever for the 512-device dry-run (HLO contains one group body,
not ``n_layers`` copies).  Remainder layers (n_layers % len(pattern)) are
unrolled.

Public API (all pure functions; ``params`` is a nested dict pytree):

- ``abstract_params(cfg)``                 -> ParamSpec tree
- ``init_params(key, cfg)``                -> fp32 parameter tree
- ``forward(params, cfg, batch, policy)``  -> (logits, aux_loss)
- ``forward_with_stats(params, cfg, batch)`` -> (logits, aux_loss, counters)
- ``loss_fn(params, cfg, batch)``          -> (loss, metrics)   [MPX-ready]
- ``abstract_cache(cfg, batch, max_seq)``  -> decode-state tree (ShapeDtype)
- ``decode(params, cfg, cache, tokens, pos)`` -> (logits, new_cache)
- ``init_paged_cache(cfg, n_pages, page_size, n_slots=...)`` -> per-layer-kind
  state-pool tree (paged K/V pools for attention layers; O(1) per-slot
  recurrent state for rglru/ssd layers)
- ``serve_forward(params, cfg, pages, table, tokens, start, valid)``
  -> (per-window-position logits (B, W, V), new_pages)
  [mixed chunked-prefill / ragged decode / speculative-verify steps,
  repro.serve — ``logit_idx`` names the W chunk positions to unembed]

Precision: the *caller* (``mpx.filter_value_and_grad``) casts params and
batch to the compute dtype; this module only pins the known-fragile spots to
fp32 (softmax, norms, router, recurrent gates/decays, softcaps, loss lse) —
exactly the paper's Example-1 discipline.

Device scopes (:func:`repro.obs.trace.scope`): ``embed``; ``layers`` around
the block scan and the unrolled tail (so the scan's stacking and copies,
and the norms and residual adds of each block, are charged to it); the
mixer and MLP calls of a block under their kind (``ssd``, ``attention``,
``rglru``, ``mlp``, ``moe``), with an expert-only block's routing, sort,
dispatch and combine under ``moe_route``; ``unembed_loss`` around the
final norm, the logits and the cross-entropy.

Remat (``cfg.remat``) wraps each block, so a block's backward holds that
block's recomputed activations only.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import mpx
from repro.configs.base import ModelConfig
from repro.nn import attention, embedding, moe as moe_lib, mlp as mlp_lib
from repro.nn import param as P
from repro.nn import rglru, ssd
from repro.nn.norms import RMS_EPS, apply_norm, norm_spec
from repro.obs.trace import scope
from repro.sharding.rules import shard

PyTree = Any


# ==========================================================================
# parameter specs
# ==========================================================================

def _block_spec(cfg: ModelConfig, kind: str) -> dict:
    d = cfg.d_model
    spec: dict = {"pre_norm": norm_spec(cfg.norm, d)}
    if kind in ("attn", "local_attn"):
        spec["attn"] = attention.attention_spec(
            d, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
            qkv_bias=cfg.qkv_bias, out_bias=cfg.out_bias)
    elif kind == "rglru":
        spec["rec"] = rglru.rglru_spec(d, cfg.d_rnn or d, cfg.conv_width)
    elif kind == "ssd":
        spec["ssd"] = ssd.ssd_spec(d, cfg.d_inner, cfg.ssm_heads,
                                   cfg.ssm_headdim, cfg.ssm_state,
                                   cfg.conv_width, cfg.ssm_groups)
    elif kind == "moe":
        spec["moe"] = moe_lib.held_experts_spec(
            d, cfg.d_ff, cfg.moe_experts, cfg.moe_held_range[1],
            cfg.moe_shared_d_ff)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    if cfg.post_norm:
        spec["post_mix_norm"] = norm_spec(cfg.norm, d)
    if cfg.mlp != "none":
        spec["mlp_norm"] = norm_spec(cfg.norm, d)
        if cfg.moe_experts > 0:
            spec["moe"] = moe_lib.moe_spec(d, cfg.d_ff, cfg.moe_experts,
                                           kind=cfg.mlp)
        else:
            spec["mlp"] = mlp_lib.mlp_spec(cfg.mlp, d, cfg.d_ff,
                                           bias=cfg.mlp_bias)
        if cfg.post_norm:
            spec["post_mlp_norm"] = norm_spec(cfg.norm, d)
    return spec


def _layout(cfg: ModelConfig) -> tuple[int, tuple[str, ...]]:
    """(n_scan_groups, remainder_kinds)."""
    lp = len(cfg.pattern)
    if not cfg.scan_layers:
        return 0, cfg.layer_kinds()
    n_groups = cfg.n_layers // lp
    rem = cfg.layer_kinds()[n_groups * lp:]
    return n_groups, rem


def abstract_params(cfg: ModelConfig) -> PyTree:
    n_groups, rem = _layout(cfg)
    spec: dict = {"embed": embedding.embedding_spec(cfg)}
    if n_groups > 0:
        group = {f"b{i}": _block_spec(cfg, kind)
                 for i, kind in enumerate(cfg.pattern)}
        spec["scan"] = P.stack_specs(group, n_groups, "layers")
    for j, kind in enumerate(rem):
        spec[f"tail{j}"] = _block_spec(cfg, kind)
    spec["final_norm"] = norm_spec(cfg.norm, cfg.d_model)
    un = embedding.unembed_spec(cfg)
    if un:
        spec["unembed"] = un
    return spec


def init_params(key: jax.Array, cfg: ModelConfig) -> PyTree:
    return P.initialize(key, abstract_params(cfg))


def param_shapes(cfg: ModelConfig) -> PyTree:
    return P.abstract(abstract_params(cfg))


def count_params(cfg: ModelConfig) -> int:
    return P.count_params(abstract_params(cfg))


# ==========================================================================
# forward (training / prefill)
# ==========================================================================

def _norm(cfg: ModelConfig, p: PyTree, x: jnp.ndarray) -> jnp.ndarray:
    return apply_norm(cfg.norm, p, x, cfg.norm_eps)


def _mixer_scope(kind: str) -> str:
    """The device scope of a layer kind's mixer."""
    return "attention" if kind in ("attn", "local_attn") else kind


def _mlp_scope(cfg: ModelConfig) -> str:
    return "moe" if cfg.moe_experts > 0 else "mlp"


def _moe_stats(cfg: ModelConfig) -> dict:
    """Counters an expert-only block adds to its step's metrics: routed
    assignments that reached a held expert and the rows of the buffers
    that took them (each summed over the blocks), and the busiest held
    expert's load over the held experts' mean (the largest over the
    blocks)."""
    if "moe" not in cfg.pattern:
        return {}
    return {"moe_held_assignments": jnp.zeros((), jnp.float32),
            "moe_held_load_max": jnp.zeros((), jnp.float32),
            "moe_dispatch_rows": jnp.zeros((), jnp.float32)}


def _block_apply(cfg: ModelConfig, kind: str, p: PyTree, x: jnp.ndarray,
                 aux: jnp.ndarray, stats: dict):
    h = shard(_norm(cfg, p["pre_norm"], x),
              ("batch", "seq", "embed"))
    with scope(_mixer_scope(kind)):
        if kind in ("attn", "local_attn"):
            y = attention.attention_apply(
                p["attn"], h, n_heads=cfg.n_heads, causal=cfg.causal,
                window=cfg.window if kind == "local_attn" else 0,
                cap=cfg.attn_softcap, rope_theta=cfg.rope_theta)
        elif kind == "rglru":
            y = rglru.rglru_block_apply(p["rec"], h,
                                        conv_width=cfg.conv_width)
        elif kind == "moe":
            y, counts = moe_lib.held_experts_apply(
                p["moe"], h, n_routed=cfg.moe_experts, top_k=cfg.moe_top_k,
                held=cfg.moe_held_range, routed_scale=cfg.moe_routed_scale)
            stats = {
                "moe_held_assignments": stats["moe_held_assignments"]
                + counts["moe_held_assignments"],
                "moe_held_load_max": jnp.maximum(
                    stats["moe_held_load_max"], counts["moe_held_load_max"]),
                "moe_dispatch_rows": stats["moe_dispatch_rows"]
                + counts["moe_dispatch_rows"]}
        else:  # ssd
            y = ssd.ssd_block_apply(p["ssd"], h, n_heads=cfg.ssm_heads,
                                    headdim=cfg.ssm_headdim,
                                    d_state=cfg.ssm_state,
                                    chunk=cfg.ssm_chunk,
                                    conv_width=cfg.conv_width,
                                    n_groups=cfg.ssm_groups,
                                    eps=cfg.norm_eps or RMS_EPS)
    if cfg.post_norm:
        y = _norm(cfg, p["post_mix_norm"], y)
    x = x + y
    if cfg.mlp != "none":
        h = shard(_norm(cfg, p["mlp_norm"], x),
                  ("batch", "seq", "embed"))
        with scope(_mlp_scope(cfg)):
            if cfg.moe_experts > 0:
                y, moe_aux = moe_lib.moe_apply(
                    p["moe"], h, n_experts=cfg.moe_experts,
                    top_k=cfg.moe_top_k, kind=cfg.mlp,
                    capacity_factor=cfg.capacity_factor)
                aux = aux + moe_aux
            else:
                y = mlp_lib.mlp_apply(cfg.mlp, p["mlp"], h)
        if cfg.post_norm:
            y = _norm(cfg, p["post_mlp_norm"], y)
        x = x + y
    return shard(x, ("batch", "seq", "embed")), aux, stats


def _remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def _embed_inputs(params, cfg: ModelConfig, batch: dict, dtype):
    """Build the (B,S,d) input sequence from the batch dict."""
    if cfg.frontend == "frames":
        return embedding.embed_frontend(params["embed"], cfg,
                                        batch["features"], dtype)
    x = embedding.embed_tokens(params["embed"], cfg, batch["inputs"], dtype)
    if cfg.frontend == "patches" and "patches" in batch:
        img = embedding.embed_frontend(params["embed"], cfg,
                                       batch["patches"], dtype)
        x = jnp.concatenate([img, x], axis=1)
    return x


def forward(params: PyTree, cfg: ModelConfig, batch: dict,
            ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """-> (logits (B,S,V) compute dtype, aux_loss fp32 scalar).

    For the VLM the returned logits cover only the text positions (the
    patch prefix is stripped before the head).
    """
    logits, aux, _ = forward_with_stats(params, cfg, batch)
    return logits, aux


def forward_with_stats(params: PyTree, cfg: ModelConfig, batch: dict,
                       ) -> tuple[jnp.ndarray, jnp.ndarray, dict]:
    """:func:`forward` plus the expert-only blocks' routing counters
    (empty without such blocks)."""
    # compute dtype is whatever the (possibly mpx-cast) params arrived in
    dtype = params["embed"][next(iter(params["embed"]))].dtype
    with scope("embed"):
        x = _embed_inputs(params, cfg, batch, dtype)
    carry = (x, jnp.zeros((), jnp.float32), _moe_stats(cfg))
    n_groups, rem = _layout(cfg)

    def block(kind: str):
        """One block as ``(carry, params) -> carry``, remat'd alone."""
        return _remat_wrap(
            cfg, lambda carry, p: _block_apply(cfg, kind, p, *carry))

    with scope("layers"):
        if n_groups > 0:
            def group_body(carry, gparams):
                for i, kind in enumerate(cfg.pattern):
                    carry = block(kind)(carry, gparams[f"b{i}"])
                return carry, None

            carry, _ = jax.lax.scan(group_body, carry, params["scan"])
        for j, kind in enumerate(rem):
            carry = block(kind)(carry, params[f"tail{j}"])
    x, aux, stats = carry

    with scope("unembed_loss"):
        x = _norm(cfg, params["final_norm"], x)
        if cfg.frontend == "patches":
            n_patch = batch["patches"].shape[1] if "patches" in batch else 0
            if n_patch:
                x = x[:, n_patch:]
        logits = embedding.logits_fn(params["embed"],
                                     params.get("unembed", {}), cfg, x)
    return logits, aux, stats


# ==========================================================================
# loss (MPX-ready: signature loss(model, batch))
# ==========================================================================

def _ce(logits: jnp.ndarray, targets: jnp.ndarray) -> jnp.ndarray:
    """Cross-entropy with fp32 log-sum-exp (fused upcast, no fp32 (B,S,V))."""
    l32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(l32, axis=-1)
    ll = jnp.take_along_axis(l32, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def make_loss_fn(cfg: ModelConfig, moe_aux_weight: float = 0.01):
    """Returns ``loss(params, batch) -> (loss, metrics)`` for mpx.filter_*."""

    def loss_fn(params, batch):
        logits, aux, stats = forward_with_stats(params, cfg, batch)
        with scope("unembed_loss"):
            ce = _ce(logits, batch["targets"])
            loss = ce + moe_aux_weight * aux
        return loss, {"ce": ce, "moe_aux": aux, **stats}

    return loss_fn


# ==========================================================================
# decode (single-token serve step)
# ==========================================================================

def _refuse_unserved(cfg: ModelConfig, kind: str) -> None:
    if kind == "moe":
        raise ValueError(
            f"{cfg.name}: layer kind 'moe' (expert-only blocks) is trained, "
            f"not served: it has no decode or serving path")


def _block_state_spec(cfg: ModelConfig, kind: str, batch: int, max_seq: int,
                      dtype):
    _refuse_unserved(cfg, kind)
    if kind in ("attn", "local_attn"):
        window = cfg.window if kind == "local_attn" else 0
        return attention.init_cache_spec(batch, max_seq, cfg.n_kv_heads,
                                         cfg.resolved_head_dim, window, dtype)
    if kind == "rglru":
        return rglru.rglru_state_spec(batch, cfg.d_rnn or cfg.d_model,
                                      cfg.conv_width, dtype)
    return ssd.ssd_state_spec(batch, cfg.d_inner, cfg.ssm_state,
                              cfg.ssm_heads, cfg.ssm_headdim,
                              cfg.conv_width, dtype)


def _stack_sds(tree: PyTree, n: int) -> PyTree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree,
        is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=jnp.bfloat16) -> PyTree:
    """Decode-state stand-ins mirroring the scan/tail parameter layout."""
    n_groups, rem = _layout(cfg)
    cache: dict = {}
    if n_groups > 0:
        group = {f"b{i}": _block_state_spec(cfg, kind, batch, max_seq, dtype)
                 for i, kind in enumerate(cfg.pattern)}
        cache["scan"] = _stack_sds(group, n_groups)
    for j, kind in enumerate(rem):
        cache[f"tail{j}"] = _block_state_spec(cfg, kind, batch, max_seq, dtype)
    return cache


def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> PyTree:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        abstract_cache(cfg, batch, max_seq, dtype),
                        is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))


def _block_decode(cfg: ModelConfig, kind: str, p: PyTree, st: PyTree,
                  x: jnp.ndarray, pos):
    _refuse_unserved(cfg, kind)
    h = _norm(cfg, p["pre_norm"], x)
    if kind in ("attn", "local_attn"):
        y, st = attention.decode_step(
            p["attn"], st, h, pos, n_heads=cfg.n_heads,
            window=cfg.window if kind == "local_attn" else 0,
            cap=cfg.attn_softcap, rope_theta=cfg.rope_theta)
    elif kind == "rglru":
        y, st = rglru.rglru_block_apply(p["rec"], h,
                                        conv_width=cfg.conv_width, state=st)
    else:
        y, st = ssd.ssd_block_apply(p["ssd"], h, n_heads=cfg.ssm_heads,
                                    headdim=cfg.ssm_headdim,
                                    d_state=cfg.ssm_state,
                                    chunk=cfg.ssm_chunk,
                                    conv_width=cfg.conv_width, state=st)
    if cfg.post_norm:
        y = _norm(cfg, p["post_mix_norm"], y)
    x = x + y
    if cfg.mlp != "none":
        h = _norm(cfg, p["mlp_norm"], x)
        if cfg.moe_experts > 0:
            y, _ = moe_lib.moe_decode_apply(
                p["moe"], h, n_experts=cfg.moe_experts, top_k=cfg.moe_top_k,
                kind=cfg.mlp)
        else:
            y = mlp_lib.mlp_apply(cfg.mlp, p["mlp"], h)
        if cfg.post_norm:
            y = _norm(cfg, p["post_mlp_norm"], y)
        x = x + y
    return x, st


# ==========================================================================
# paged serving path (chunked prefill + ragged decode, repro.serve)
# ==========================================================================

_SERVABLE_KINDS = ("attn", "local_attn", "rglru", "ssd")
_RECURRENT_KINDS = ("rglru", "ssd")


def _require_paged_support(cfg: ModelConfig) -> None:
    bad = [k for k in cfg.layer_kinds() if k not in _SERVABLE_KINDS]
    if bad:
        _refuse_unserved(cfg, bad[0])
        raise ValueError(
            f"{cfg.name}: layer kind {bad[0]!r} has no serving state-pool "
            f"implementation; the paged state pool serves attention "
            f"(paged KV: 'attn', 'local_attn') and recurrent "
            f"(O(1) per-slot state: 'rglru', 'ssd') layer families")


def _pool_leaf_spec(cfg: ModelConfig, kind: str, n_pages: int,
                    page_size: int, n_slots: int, dtype,
                    kv_format: str) -> PyTree:
    """Per-layer-kind state-pool leaf: paged KV or per-slot decode state."""
    if kind in ("attn", "local_attn"):
        return attention.paged_cache_spec(
            n_pages, page_size, cfg.n_kv_heads, cfg.resolved_head_dim,
            dtype, kv_format=kv_format)
    return _block_state_spec(cfg, kind, n_slots, 0, dtype)


def abstract_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                         dtype=jnp.bfloat16, kv_format: str = "bf16",
                         n_slots: int = 1) -> PyTree:
    """Per-layer-kind state-pool stand-ins mirroring the scan/tail layout.

    Attention layers get one head-major (n_pages, K, page_size, D) pool
    pair each; all of them share one page table (each has its own pool
    array), so the serve scheduler allocates pages once per sequence.  A quantized
    ``kv_format`` ("i8", "f8_e4m3", "f8_e3m4" — see :mod:`repro.quant`)
    stores those pools in the format's storage dtype and adds a
    (n_pages, K) fp32 amax-scale sidecar pair per layer; ``dtype`` then
    only names the bf16 passthrough layout.

    Recurrent layers ('rglru', 'ssd') instead carry O(1) per-slot decode
    state — batch dim ``n_slots``, no pages, no page-table entries: the
    RG-LRU hidden state and the SSD state accumulator stay fp32 (the MPX
    fragile-spot policy), conv buffers ride ``dtype``.  Scan groups carry
    the usual stacked leading dim over both kinds of leaves.
    """
    _require_paged_support(cfg)
    n_groups, rem = _layout(cfg)
    leaf = lambda kind: _pool_leaf_spec(  # noqa: E731
        cfg, kind, n_pages, page_size, n_slots, dtype, kv_format)
    cache: dict = {}
    if n_groups > 0:
        group = {f"b{i}": leaf(kind)
                 for i, kind in enumerate(cfg.pattern)}
        cache["scan"] = _stack_sds(group, n_groups)
    for j, kind in enumerate(rem):
        cache[f"tail{j}"] = leaf(kind)
    return cache


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=jnp.bfloat16, kv_format: str = "bf16",
                     n_slots: int = 1) -> PyTree:
    # scale sidecars init to the quant SCALE_FLOOR via zeros -> floor is
    # irrelevant: zero pages dequantize to zero under any scale, and the
    # first write to a page installs a fresh amax scale
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        abstract_paged_cache(cfg, n_pages, page_size, dtype,
                                             kv_format=kv_format,
                                             n_slots=n_slots),
                        is_leaf=lambda s: isinstance(s, jax.ShapeDtypeStruct))


def slot_state_mask(cfg: ModelConfig, kv_format: str = "bf16") -> PyTree:
    """Bool tree matching :func:`abstract_paged_cache`'s structure: True on
    per-slot recurrent state leaves (slot-indexed, reset on admit), False
    on paged KV pool leaves (page-indexed, recycled by the allocator)."""
    _require_paged_support(cfg)
    n_groups, rem = _layout(cfg)
    is_sds = lambda s: isinstance(s, jax.ShapeDtypeStruct)  # noqa: E731
    leaf = lambda kind: jax.tree.map(  # noqa: E731
        lambda _: kind in _RECURRENT_KINDS,
        _pool_leaf_spec(cfg, kind, 1, 1, 1, jnp.bfloat16, kv_format),
        is_leaf=is_sds)
    mask: dict = {}
    if n_groups > 0:
        mask["scan"] = {f"b{i}": leaf(kind)
                        for i, kind in enumerate(cfg.pattern)}
    for j, kind in enumerate(rem):
        mask[f"tail{j}"] = leaf(kind)
    return mask


def _block_serve(cfg: ModelConfig, kind: str, p: PyTree, pages: dict,
                 page_table, x: jnp.ndarray, positions, valid, *,
                 page_size: int, use_kernel: bool, pages_per_block: int = 1,
                 kv_format: str = "bf16"):
    _refuse_unserved(cfg, kind)
    h = _norm(cfg, p["pre_norm"], x)
    with scope(_mixer_scope(kind)):
        if kind in ("attn", "local_attn"):
            y, pages = attention.paged_attend(
                p["attn"], pages, page_table, h, positions, valid,
                page_size=page_size, n_heads=cfg.n_heads,
                window=cfg.window if kind == "local_attn" else 0,
                cap=cfg.attn_softcap, rope_theta=cfg.rope_theta,
                use_kernel=use_kernel, pages_per_block=pages_per_block,
                kv_format=kv_format)
        elif kind == "rglru":
            y, pages = rglru.rglru_serve_chunk(p["rec"], h, pages, valid,
                                               conv_width=cfg.conv_width)
        else:  # ssd
            y, pages = ssd.ssd_serve_chunk(p["ssd"], h, pages, valid,
                                           n_heads=cfg.ssm_heads,
                                           headdim=cfg.ssm_headdim,
                                           d_state=cfg.ssm_state,
                                           conv_width=cfg.conv_width)
    if cfg.post_norm:
        y = _norm(cfg, p["post_mix_norm"], y)
    x = x + y
    if cfg.mlp != "none":
        h = _norm(cfg, p["mlp_norm"], x)
        with scope(_mlp_scope(cfg)):
            if cfg.moe_experts > 0:
                y, _ = moe_lib.moe_decode_apply(
                    p["moe"], h, n_experts=cfg.moe_experts,
                    top_k=cfg.moe_top_k, kind=cfg.mlp)
            else:
                y = mlp_lib.mlp_apply(cfg.mlp, p["mlp"], h)
        if cfg.post_norm:
            y = _norm(cfg, p["post_mlp_norm"], y)
        x = x + y
    return x, pages


def serve_forward(params: PyTree, cfg: ModelConfig, pages: PyTree,
                  page_table: jnp.ndarray, tokens: jnp.ndarray,
                  start: jnp.ndarray, valid: jnp.ndarray, *,
                  page_size: int, logit_idx: Optional[jnp.ndarray] = None,
                  use_kernel: bool = False, pages_per_block: int = 1,
                  kv_format: str = "bf16") -> tuple[jnp.ndarray, PyTree]:
    """Unified serving step over the per-layer-kind state pool.

    tokens (B, C) with per-slot chunk ``start`` positions (B,) and ``valid``
    (B,) real-token counts (0 disables a slot).  Each slot is independent:
    one (B, C) step can mix prefill chunks (valid up to C), single decode
    tokens (valid = 1, start = current length) and speculative decode
    windows (valid = 1 + k: the committed token plus k proposed drafts) —
    the mixed-chunk plans :mod:`repro.serve.scheduler` emits.

    Attention layers scatter K/V into their paged pools and attend through
    the shared ``page_table``; recurrent layers ('rglru', 'ssd') ignore the
    table entirely and advance their O(1) per-slot state (batch row b IS
    slot b) via the ``*_serve_chunk`` entry points, whose masked
    per-position scans make padded chunk columns exact state no-ops — so
    greedy serving stays token-identical to per-token :func:`decode`
    across attn / ssm / rglru / hybrid stacks.

    Returns (logits (B, W, V), new pages): per-slot logits for the W chunk
    positions named by ``logit_idx`` (B, W) int32 — the slot's live window
    for speculative verification, or (the default when ``logit_idx`` is
    None) just each slot's last valid position with W = 1.  Gathering the
    window *before* the unembed keeps the (d, V) projection at W columns
    per slot instead of once per padded chunk position — the C-fold
    vocab-matmul saving survives speculation because W (typically <= 5) is
    far below C.

    ``use_kernel=True`` runs every full-attention layer through the Pallas
    paged-attention kernel (:mod:`repro.kernels.paged_attention`) —
    prefill, decode, mixed and speculative-window plans alike, one
    compiled program, no gathered dense copy of the cache;
    ``pages_per_block`` widens the kernel's K-blocks to span that many
    logical pages per grid step.

    ``kv_format`` ("bf16" | "i8" | "f8_e4m3" | "f8_e3m4", see
    :mod:`repro.quant`) must match the layout ``pages`` was built with
    (:func:`init_paged_cache`): quantized formats write-quantize each
    chunk's K/V into the pools (per-page/per-head amax scales in the
    fp32 sidecars) and dequantize on read — inside the kernel's VMEM
    blocks on the ``use_kernel`` path, so the sub-bf16 pool is the ONLY
    HBM-resident image of the cache.
    """
    _require_paged_support(cfg)
    dtype = params["embed"][next(iter(params["embed"]))].dtype
    with scope("embed"):
        x = embedding.embed_tokens(params["embed"], cfg, tokens, dtype)
    positions = start[:, None] + jnp.arange(tokens.shape[1])[None, :]
    n_groups, rem = _layout(cfg)
    new_pages: dict = {}

    with scope("layers"):
        if n_groups > 0:
            def group_body(x, scanned):
                gparams, gpages = scanned
                new_gpages = {}
                for i, kind in enumerate(cfg.pattern):
                    x, new_gpages[f"b{i}"] = _block_serve(
                        cfg, kind, gparams[f"b{i}"], gpages[f"b{i}"],
                        page_table, x, positions, valid,
                        page_size=page_size, use_kernel=use_kernel,
                        pages_per_block=pages_per_block,
                        kv_format=kv_format)
                return x, new_gpages

            x, new_pages["scan"] = jax.lax.scan(
                group_body, x, (params["scan"], pages["scan"]))
        for j, kind in enumerate(rem):
            x, new_pages[f"tail{j}"] = _block_serve(
                cfg, kind, params[f"tail{j}"], pages[f"tail{j}"],
                page_table, x, positions, valid,
                page_size=page_size, use_kernel=use_kernel,
                pages_per_block=pages_per_block, kv_format=kv_format)

    # gather the sampled window positions before the unembed so the (d, V)
    # projection runs over W positions per slot, not per padded chunk
    # position (C-fold less vocab-matmul work per step)
    with scope("unembed_loss"):
        if logit_idx is None:
            logit_idx = jnp.clip(valid - 1, 0)[:, None]      # (B, 1)
        x = x[jnp.arange(x.shape[0])[:, None], logit_idx]    # (B, W, d)
        x = _norm(cfg, params["final_norm"], x)
        logits = embedding.logits_fn(params["embed"],
                                     params.get("unembed", {}), cfg, x)
    return logits, new_pages


def decode(params: PyTree, cfg: ModelConfig, cache: PyTree,
           tokens: jnp.ndarray, pos) -> tuple[jnp.ndarray, PyTree]:
    """One token for every sequence: tokens (B,1) -> logits (B,1,V)."""
    dtype = params["embed"][next(iter(params["embed"]))].dtype
    x = embedding.embed_tokens(params["embed"], cfg, tokens, dtype)
    n_groups, rem = _layout(cfg)
    new_cache: dict = {}

    if n_groups > 0:
        def group_body(x, scanned):
            gparams, gcache = scanned
            new_gcache = {}
            for i, kind in enumerate(cfg.pattern):
                x, st = _block_decode(cfg, kind, gparams[f"b{i}"],
                                      gcache[f"b{i}"], x, pos)
                new_gcache[f"b{i}"] = st
            return x, new_gcache

        x, new_cache["scan"] = jax.lax.scan(
            group_body, x, (params["scan"], cache["scan"]))
    for j, kind in enumerate(rem):
        x, new_cache[f"tail{j}"] = _block_decode(
            cfg, kind, params[f"tail{j}"], cache[f"tail{j}"], x, pos)

    x = _norm(cfg, params["final_norm"], x)
    logits = embedding.logits_fn(params["embed"], params.get("unembed", {}),
                                 cfg, x)
    return logits, new_cache
