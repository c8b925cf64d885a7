"""Paged-attention Pallas kernel (interpret mode) vs the ragged paged oracle.

Covers the contract the serving engine relies on: identity with
``kernels.ref.paged_attention_ref`` across ragged mixed prefill+decode
batches (idle ``valid=0`` slots, sentinel page-table entries, multiple
page sizes, GQA, ``C>1`` chunks), never reading pages the scheduler never
allocated (NaN-poisoned free pages), permutation-invariance over physical
page placement (hypothesis), and — the tentpole acceptance — that
``serve_forward(use_kernel=True)`` traces with NO gathered dense
``(B, Pmax*page_size, K, D)`` intermediate.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.kernels import ref as kref
from repro.kernels.paged_attention import paged_attention
from repro.quant import ops as qops


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 2e-5


def _random_paged_case(key, b, c, h, kv, d, n_pages, page_size, pmax,
                       start, valid, dtype, permute_seed=0):
    """Build (q, pools, table) with each slot's prefix scattered into
    randomly chosen physical pages; returns NaN in every free page."""
    ks = jax.random.split(jax.random.key(key), 3)
    q = jax.random.normal(ks[0], (b, c, h, d), dtype)
    rng = np.random.default_rng(permute_seed)
    perm = rng.permutation(n_pages)
    table = np.full((b, pmax), n_pages, np.int32)        # sentinel
    used = 0
    for s in range(b):
        need = -(-(int(start[s]) + int(valid[s])) // page_size)
        table[s, :need] = perm[used:used + need]
        used += need
    # dense logical content, scattered through the table page by page
    k_dense = jax.random.normal(ks[1], (b, pmax * page_size, kv, d), dtype)
    v_dense = jax.random.normal(ks[2], (b, pmax * page_size, kv, d), dtype)
    pools_k = jnp.full((n_pages, kv, page_size, d), jnp.nan, dtype)
    pools_v = jnp.full((n_pages, kv, page_size, d), jnp.nan, dtype)
    for s in range(b):
        length = int(start[s]) + int(valid[s])
        for pg in range(-(-length // page_size)):
            lo = pg * page_size
            n = min(page_size, length - lo)
            phys = int(table[s, pg])
            pools_k = pools_k.at[phys, :, :n].set(
                k_dense[s, lo:lo + n].swapaxes(0, 1))
            pools_v = pools_v.at[phys, :, :n].set(
                v_dense[s, lo:lo + n].swapaxes(0, 1))
            # allocated-page tails must be benign, not NaN: probs there
            # are exactly 0 but 0 * NaN would still poison the row sum
            if n < page_size:
                pools_k = pools_k.at[phys, :, n:].set(0)
                pools_v = pools_v.at[phys, :, n:].set(0)
    return q, pools_k, pools_v, jnp.asarray(table)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("page_size", [8, 16])
@pytest.mark.parametrize("c", [1, 8])
def test_paged_kernel_vs_ref_ragged(dtype, page_size, c):
    """Mixed batch: prefill chunk, mid-stream decode, fresh decode, idle."""
    b, h, kv, d = 4, 8, 2, 32
    pmax = 6
    n_pages = 4 * pmax
    start = np.array([11, 2 * page_size + 3, 0, 0], np.int32)
    valid = np.array([c, 1, 1, 0], np.int32)
    q, pk, pv, table = _random_paged_case(
        0, b, c, h, kv, d, n_pages, page_size, pmax, start, valid, dtype)
    got = paged_attention(q, pk, pv, table, jnp.asarray(start),
                          jnp.asarray(valid), interpret=True)
    want = kref.paged_attention_ref(q, pk, pv, table, jnp.asarray(start),
                                    jnp.asarray(valid))
    got = np.asarray(got, np.float32)
    # free pages are NaN: any read of an unallocated page poisons the out
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    # idle slot and padding chunk positions are exact zeros
    assert (got[3] == 0).all()
    if c > 1:
        assert (got[1, 1:] == 0).all() and (got[2, 1:] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ppb", [1, 2, 4])
def test_paged_kernel_pages_per_block_parity(dtype, ppb):
    """Multi-page K-blocks (pages_per_block logical pages concatenated per
    grid step — the MXU-lane-filling follow-on) are numerically identical
    to the one-page-per-step kernel and to the ragged oracle, including
    ragged tails where a block straddles a slot's length and blocks whose
    later sub-pages fall entirely past it."""
    b, c, h, kv, d = 4, 8, 8, 2, 32
    page_size, pmax = 8, 6
    n_pages = 4 * pmax
    # lengths chosen to land mid-page, mid-block and at block boundaries
    start = np.array([11, 2 * page_size + 3, 0, 0], np.int32)
    valid = np.array([c, 1, 1, 0], np.int32)
    q, pk, pv, table = _random_paged_case(
        0, b, c, h, kv, d, n_pages, page_size, pmax, start, valid, dtype)
    got = paged_attention(q, pk, pv, table, jnp.asarray(start),
                          jnp.asarray(valid), pages_per_block=ppb,
                          interpret=True)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()       # NaN-poisoned free pages never read
    want = kref.paged_attention_ref(q, pk, pv, table, jnp.asarray(start),
                                    jnp.asarray(valid))
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    base = paged_attention(q, pk, pv, table, jnp.asarray(start),
                           jnp.asarray(valid), pages_per_block=1,
                           interpret=True)
    if ppb > 1 and dtype == jnp.float32:
        # widening the block changes the summation grouping, not the math
        np.testing.assert_allclose(got, np.asarray(base, np.float32),
                                   atol=1e-6, rtol=1e-6)
    assert (got[3] == 0).all()          # idle slot stays exact zeros


def test_paged_kernel_pages_per_block_clamps_to_pmax():
    """pages_per_block beyond the table width degrades to one grid step
    spanning every logical page."""
    b, c, h, kv, d, page_size, pmax = 2, 4, 4, 2, 16, 8, 4
    start = np.array([5, 9], np.int32)
    valid = np.array([c, 1], np.int32)
    q, pk, pv, table = _random_paged_case(
        1, b, c, h, kv, d, 3 * pmax, page_size, pmax, start, valid,
        jnp.float32)
    got = paged_attention(q, pk, pv, table, jnp.asarray(start),
                          jnp.asarray(valid), pages_per_block=64,
                          interpret=True)
    want = kref.paged_attention_ref(q, pk, pv, table, jnp.asarray(start),
                                    jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="pages_per_block"):
        paged_attention(q, pk, pv, table, start, valid, pages_per_block=0,
                        interpret=True)


def test_paged_kernel_gqa_and_mha():
    """K == H (no grouping) and K < H (group resident) both match."""
    b, c, d, page_size, pmax = 2, 4, 16, 8, 4
    start = np.array([5, 9], np.int32)
    valid = np.array([c, 1], np.int32)
    for h, kv in ((4, 4), (8, 2), (6, 1)):
        q, pk, pv, table = _random_paged_case(
            h, b, c, h, kv, d, 3 * pmax, page_size, pmax, start, valid,
            jnp.float32)
        got = paged_attention(q, pk, pv, table, jnp.asarray(start),
                              jnp.asarray(valid), interpret=True)
        want = kref.paged_attention_ref(q, pk, pv, table,
                                        jnp.asarray(start),
                                        jnp.asarray(valid))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-5, rtol=2e-5)


def test_paged_kernel_matches_contiguous_decode():
    """C=1 decode vs the dense ragged decode oracle — same numbers the old
    gather+decode_attention path produced."""
    b, h, kv, d, page_size, pmax = 3, 4, 2, 32, 8, 4
    lengths = np.array([1, 13, 30], np.int32)
    start, valid = lengths - 1, np.ones(b, np.int32)
    q, pk, pv, table = _random_paged_case(
        7, b, 1, h, kv, d, 2 * pmax, page_size, pmax, start, valid,
        jnp.float32)
    got = paged_attention(q, pk, pv, table, jnp.asarray(start),
                          jnp.asarray(valid), interpret=True)
    tbl = jnp.clip(table, 0, 2 * pmax - 1)
    k = jnp.nan_to_num(
        pk[tbl].swapaxes(2, 3).reshape(b, pmax * page_size, kv, d))
    v = jnp.nan_to_num(
        pv[tbl].swapaxes(2, 3).reshape(b, pmax * page_size, kv, d))
    want = kref.decode_attention_ref(q[:, 0], k, v, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got[:, 0], np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_page_table_permutation_property():
    """Physical page placement is invisible: any permutation of the pool
    yields identical outputs (hypothesis over permutations + lengths)."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    b, c, h, kv, d, page_size, pmax = 2, 4, 4, 2, 16, 8, 4
    n_pages = 3 * pmax

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           start0=st.integers(0, 3 * 8 - 4),
           valid1=st.integers(0, 4))
    def prop(seed, start0, valid1):
        start = np.array([start0, 7], np.int32)
        valid = np.array([c, valid1], np.int32)
        q, pk, pv, table = _random_paged_case(
            3, b, c, h, kv, d, n_pages, page_size, pmax, start, valid,
            jnp.float32, permute_seed=seed)
        got = paged_attention(q, pk, pv, table, jnp.asarray(start),
                              jnp.asarray(valid), interpret=True)
        want = kref.paged_attention_ref(q, pk, pv, table,
                                        jnp.asarray(start),
                                        jnp.asarray(valid))
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-5, rtol=2e-5)

    prop()


# --------------------------------------------------------------------------
# quantized pools: in-kernel dequant vs the quantized ragged oracle
# --------------------------------------------------------------------------

def _quantize_case(q, pk, pv, table, fmt):
    """Quantize a `_random_paged_case`'s pools page by page.

    Allocated pages get per-(page, head) amax scales; free pages keep
    NaN values AND get NaN scales, so any kernel read of an unallocated
    page (values or sidecar) poisons the output and fails the isfinite
    assert."""
    n_pages, kv, ps, d = pk.shape
    alloc = np.unique(np.asarray(table)[np.asarray(table) < n_pages])
    qk = jnp.zeros((n_pages, kv, ps, d), fmt.storage_dtype())
    qv = jnp.zeros_like(qk)
    if fmt.kind == "float":             # NaN representable: poison pools
        qk = jnp.full_like(qk, jnp.nan)
        qv = jnp.full_like(qv, jnp.nan)
    sk = jnp.full((n_pages, kv), jnp.nan, jnp.float32)
    sv = jnp.full_like(sk, jnp.nan)
    for pg in alloc:
        pg = int(pg)
        kb = jnp.nan_to_num(pk[pg]).astype(jnp.float32)
        vb = jnp.nan_to_num(pv[pg]).astype(jnp.float32)
        ksc = qops.amax_scale(kb, fmt, axes=(1, 2))
        vsc = qops.amax_scale(vb, fmt, axes=(1, 2))
        qk = qk.at[pg].set(qops.quantize(kb, ksc[:, None, None], fmt))
        qv = qv.at[pg].set(qops.quantize(vb, vsc[:, None, None], fmt))
        sk = sk.at[pg].set(ksc)
        sv = sv.at[pg].set(vsc)
    return qk, qv, sk, sv


@pytest.mark.parametrize("fmt_name", ["i8", "f8_e4m3", "f8_e3m4"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("c", [1, 8])
def test_quantized_paged_kernel_vs_quantized_ref(fmt_name, dtype, c):
    """TENTPOLE: the kernel's in-VMEM dequant (each sub-page's scale
    selected from the resident per-head row by the same logical->physical
    rule as its values)
    matches the quantized ragged oracle across the same mixed batch the
    bf16 tests pin — prefill chunk, mid-stream decode, fresh decode,
    idle slot — and never touches an unallocated page's values OR
    scales (both NaN-poisoned)."""
    fmt = quant.resolve(fmt_name)
    b, h, kv, d = 4, 8, 2, 32
    page_size, pmax = 8, 6
    n_pages = 4 * pmax
    start = np.array([11, 2 * page_size + 3, 0, 0], np.int32)
    valid = np.array([c, 1, 1, 0], np.int32)
    q, pk, pv, table = _random_paged_case(
        0, b, c, h, kv, d, n_pages, page_size, pmax, start, valid, dtype)
    qk, qv, sk, sv = _quantize_case(q, pk, pv, table, fmt)
    got = paged_attention(q, qk, qv, table, jnp.asarray(start),
                          jnp.asarray(valid), k_scales=sk, v_scales=sv,
                          interpret=True)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    want = kref.quantized_paged_attention_ref(
        q, qk, qv, sk, sv, table, jnp.asarray(start), jnp.asarray(valid))
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    assert (got[3] == 0).all()          # idle slot: exact zeros
    if c > 1:
        assert (got[1, 1:] == 0).all() and (got[2, 1:] == 0).all()


@pytest.mark.parametrize("ppb", [2, 4])
def test_quantized_kernel_pages_per_block_parity(ppb):
    """Multi-page K-blocks dequantize each sub-page with its OWN page's
    scale before the VMEM concatenation — parity with ppb=1 and with the
    oracle on ragged lengths that straddle block boundaries."""
    fmt = quant.I8
    b, c, h, kv, d = 4, 8, 8, 2, 32
    page_size, pmax = 8, 6
    start = np.array([11, 2 * page_size + 3, 0, 0], np.int32)
    valid = np.array([c, 1, 1, 0], np.int32)
    q, pk, pv, table = _random_paged_case(
        0, b, c, h, kv, d, 4 * pmax, page_size, pmax, start, valid,
        jnp.float32)
    qk, qv, sk, sv = _quantize_case(q, pk, pv, table, fmt)
    args = (q, qk, qv, table, jnp.asarray(start), jnp.asarray(valid))
    got = paged_attention(*args, k_scales=sk, v_scales=sv,
                          pages_per_block=ppb, interpret=True)
    base = paged_attention(*args, k_scales=sk, v_scales=sv,
                           pages_per_block=1, interpret=True)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(base, np.float32),
                               atol=1e-6, rtol=1e-6)
    want = kref.quantized_paged_attention_ref(
        q, qk, qv, sk, sv, table, jnp.asarray(start), jnp.asarray(valid))
    np.testing.assert_allclose(got, np.asarray(want, np.float32),
                               atol=2e-5, rtol=2e-5)


def test_quantized_kernel_requires_both_scales():
    b, c, h, kv, d, page_size, pmax = 2, 1, 4, 2, 16, 8, 2
    start = np.array([3, 0], np.int32)
    valid = np.array([1, 0], np.int32)
    q, pk, pv, table = _random_paged_case(
        5, b, c, h, kv, d, 2 * pmax, page_size, pmax, start, valid,
        jnp.float32)
    with pytest.raises(ValueError, match="together"):
        paged_attention(q, pk, pv, table, start, valid,
                        k_scales=jnp.ones((2 * pmax, kv)), interpret=True)


# --------------------------------------------------------------------------
# acceptance: the traced serve step has no gathered dense intermediate
# --------------------------------------------------------------------------

def _serve_jaxpr(use_kernel, kv_format="bf16"):
    from repro import mpx
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T

    cfg = ModelConfig(
        name="jaxpr-probe", family="dense",
        n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
        d_ff=64, vocab_size=64, pattern=("attn",), mlp="swiglu",
        tie_embeddings=True, remat="none")
    b, pmax, page_size = 3, 5, 8
    params = mpx.cast_to_bfloat16(T.init_params(jax.random.key(0), cfg))
    pages = T.init_paged_cache(cfg, n_pages=b * pmax, page_size=page_size,
                               kv_format=kv_format)
    table = jnp.zeros((b, pmax), jnp.int32)
    tokens = jnp.zeros((b, 4), jnp.int32)
    start = jnp.zeros((b,), jnp.int32)
    valid = jnp.ones((b,), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, pg, tb, tk, st, vl: T.serve_forward(
            p, cfg, pg, tb, tk, st, vl, page_size=page_size,
            use_kernel=use_kernel, kv_format=kv_format))(
        params, pages, table, tokens, start, valid)
    # the gathered contiguous view is (B, Pmax*page_size, K, D)
    dense = re.compile(r"\[3,40,2,8\]")
    return dense.search(str(jaxpr)) is not None


def test_serve_forward_use_kernel_never_gathers():
    assert _serve_jaxpr(use_kernel=False)      # probe is valid: gather path
    assert not _serve_jaxpr(use_kernel=True)   # kernel path: no dense copy


@pytest.mark.parametrize("kv_format", ["i8", "f8_e4m3"])
def test_serve_forward_quantized_kernel_never_materializes_dense(kv_format):
    """ACCEPTANCE: with a quantized KV format the kernel path still traces
    with NO (B, Pmax*page_size, K, D) aval of ANY dtype — neither a
    gathered pool copy nor a dense dequantized bf16 view (dequant happens
    block-by-block in VMEM; write-requantization touches only the
    chunk's (B, wp, page_size, K, D) pages).  The gather fallback DOES
    materialize it — which is what validates the probe."""
    assert _serve_jaxpr(use_kernel=False, kv_format=kv_format)
    assert not _serve_jaxpr(use_kernel=True, kv_format=kv_format)
