"""repro.quant: formats, quantize/dequantize ops vs loop references,
the policy kv= component, and the serving-bench artifact schema."""
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import mpx, quant
from repro.quant import ops as qops
from repro.quant import reference as qref

QUANT_FORMATS = ("i8", "f8_e4m3", "f8_e3m4")

#: worst-case round-trip error of one value under amax scaling: int8 is a
#: uniform grid (half a step = scale/2); fp8 rounds to 2^-(mantissa+1)
#: RELATIVE error, so the bound scales with |x| (plus a scale-sized floor
#: for the subnormal range).
_MANTISSA = {"f8_e4m3": 3, "f8_e3m4": 4}


def _roundtrip_bound(x, scale, fmt):
    if fmt.kind == "int":
        return np.full_like(x, scale * 0.5 + 1e-7)
    return np.abs(x) * 2.0 ** -(_MANTISSA[fmt.name] + 1) + scale


# --------------------------------------------------------------------------
# formats
# --------------------------------------------------------------------------

def test_format_registry_and_aliases():
    assert quant.resolve("i8") is quant.I8
    assert quant.resolve("int8") is quant.I8
    assert quant.resolve("fp8") is quant.F8_E4M3
    assert quant.resolve("e3m4") is quant.F8_E3M4
    assert quant.resolve(None) is quant.BF16
    assert quant.resolve(quant.I8) is quant.I8
    assert not quant.BF16.quantized and quant.I8.quantized
    assert quant.I8.itemsize == 1 and quant.BF16.itemsize == 2
    with pytest.raises(ValueError, match="unknown KV format"):
        quant.resolve("i4")


def test_storage_dtype_fp8_emulates_in_bf16_off_tpu():
    """Off-TPU the fp8 pools store in bf16 — exactly, because every fp8
    value is representable in bf16 (the emulation contract)."""
    assert quant.I8.storage_dtype("cpu") == jnp.int8
    assert quant.F8_E4M3.storage_dtype("cpu") == jnp.bfloat16
    assert quant.F8_E4M3.storage_dtype("tpu") == jnp.float8_e4m3fn
    assert quant.F8_E3M4.storage_dtype("tpu") == jnp.float8_e3m4
    x = jax.random.normal(jax.random.key(0), (4096,), jnp.float32) * 40
    for fmt in (quant.F8_E4M3, quant.F8_E3M4):
        scale = float(qops.amax_scale(x, fmt, axes=0))
        native = (x / scale).astype(fmt.grid_dtype).astype(jnp.float32)
        emulated = qops.quantize(x, scale, fmt).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(native),
                                      np.asarray(emulated))


def test_pool_spec_container_layout():
    spec = quant.pool_spec(12, 16, 4, 32, "bf16")
    assert set(spec) == {"k", "v"}
    assert spec["k"].shape == (12, 4, 16, 32)       # head-major (P, K, ps, D)
    assert spec["k"].dtype == jnp.bfloat16
    spec = quant.pool_spec(12, 16, 4, 32, "i8")
    assert set(spec) == {"k", "v", "k_scale", "v_scale"}
    assert spec["k"].dtype == jnp.int8
    assert spec["k_scale"].shape == (12, 4)
    assert spec["k_scale"].dtype == jnp.float32


def test_max_write_pages():
    # a C-token contiguous range straddles at most (C-1)//ps + 2 pages
    assert qops.max_write_pages(1, 16, 8) == 2
    assert qops.max_write_pages(16, 16, 8) == 2
    assert qops.max_write_pages(17, 16, 8) == 3
    assert qops.max_write_pages(64, 16, 2) == 2     # clamped to pmax


# --------------------------------------------------------------------------
# quantize / dequantize
# --------------------------------------------------------------------------

@pytest.mark.parametrize("fmt_name", QUANT_FORMATS)
def test_quantize_matches_numpy_reference(fmt_name):
    fmt = quant.resolve(fmt_name)
    x = jax.random.normal(jax.random.key(1), (512,), jnp.float32) * 7
    scale = float(qops.amax_scale(x, fmt, axes=0))
    got = np.asarray(qops.quantize(x, scale, fmt).astype(jnp.float32))
    want = np.asarray(qref.quantize_ref(np.asarray(x), scale, fmt),
                      np.float32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fmt_name", QUANT_FORMATS)
def test_roundtrip_error_bound(fmt_name):
    fmt = quant.resolve(fmt_name)
    x = np.asarray(jax.random.normal(jax.random.key(2), (4096,),
                                     jnp.float32) * 3)
    scale = max(np.abs(x).max() / fmt.fmax, qops.SCALE_FLOOR)
    deq = np.asarray(qops.dequantize(qops.quantize(jnp.asarray(x), scale,
                                                   fmt), scale))
    err = np.abs(deq - x)
    assert (err <= _roundtrip_bound(x, scale, fmt)).all()
    # zeros survive exactly, whatever the scale floor does
    z = qops.dequantize(qops.quantize(jnp.zeros(8), 1.0, fmt), 1.0)
    assert (np.asarray(z) == 0).all()


def test_quantize_rejects_passthrough():
    with pytest.raises(ValueError, match="passthrough"):
        qops.quantize(jnp.ones(4), 1.0, "bf16")


# --------------------------------------------------------------------------
# quantized paged write (write-quantize contract)
# --------------------------------------------------------------------------

def _write_case(fmt, seed=0):
    """Mixed batch: a page-straddling prefill chunk into a partially
    pre-populated page, a single decode token, an idle slot."""
    rng = np.random.default_rng(seed)
    P, ps, K, D = 10, 8, 2, 4
    B, C, pmax = 3, 6, 4
    table = np.full((B, pmax), P, np.int32)
    table[0, :3] = [2, 5, 7]
    table[1, :2] = [1, 9]
    start = np.array([5, 9, 0], np.int32)
    valid = np.array([6, 1, 0], np.int32)
    positions = start[:, None] + np.arange(C)[None, :]
    vals = jnp.asarray(rng.normal(size=(B, C, K, D)), jnp.bfloat16)

    pages = jnp.zeros((P, K, ps, D), fmt.storage_dtype())
    scales = jnp.full((P, K), qops.SCALE_FLOOR, jnp.float32)
    # pre-populate slot 0's first written page with quantized content
    pre = jnp.asarray(rng.normal(size=(K, ps, D)), jnp.float32)
    s_pre = qops.amax_scale(pre, fmt, axes=(1, 2))
    pages = pages.at[2].set(qops.quantize(pre, s_pre[:, None, None], fmt))
    scales = scales.at[2].set(s_pre)
    return (pages, scales, vals, jnp.asarray(table), jnp.asarray(positions),
            jnp.asarray(valid), ps, table, positions, valid)


@pytest.mark.parametrize("fmt_name", QUANT_FORMATS)
def test_quantized_paged_write_matches_loop_reference(fmt_name):
    fmt = quant.resolve(fmt_name)
    (pages, scales, vals, table_j, pos_j, valid_j, ps,
     table, positions, valid) = _write_case(fmt)
    got_p, got_s = qops.quantized_paged_write(
        pages, scales, vals, table_j, pos_j, valid_j, page_size=ps, fmt=fmt)
    ref_p, ref_s = qref.quantized_paged_write_ref(
        pages, scales, np.asarray(vals.astype(jnp.float32)),
        table, positions, valid, page_size=ps, fmt=fmt)
    np.testing.assert_array_equal(
        np.asarray(got_p.astype(jnp.float32)), ref_p)
    np.testing.assert_array_equal(np.asarray(got_s), ref_s)


@pytest.mark.parametrize("fmt_name", QUANT_FORMATS)
def test_quantized_paged_write_untouched_pages_bitwise(fmt_name):
    """Only the pages the chunk touches may change — bits and scales of
    every other page are identical (requantization never leaks)."""
    fmt = quant.resolve(fmt_name)
    (pages, scales, vals, table_j, pos_j, valid_j, ps,
     table, positions, valid) = _write_case(fmt)
    got_p, got_s = qops.quantized_paged_write(
        pages, scales, vals, table_j, pos_j, valid_j, page_size=ps, fmt=fmt)
    touched = set()
    for s in range(len(valid)):
        for t in range(valid[s]):
            touched.add(int(table[s, positions[s, t] // ps]))
    for pg in range(pages.shape[0]):
        if pg in touched:
            continue
        np.testing.assert_array_equal(
            np.asarray(got_p[pg].astype(jnp.float32)),
            np.asarray(pages[pg].astype(jnp.float32)))
        np.testing.assert_array_equal(np.asarray(got_s[pg]),
                                      np.asarray(scales[pg]))
    # slot 0 writes positions 5..10 (phys 2 and 5), slot 1 position 9
    # (phys 9); phys 7 (slot 0's reserved-but-unwritten page) stays put
    assert touched == {2, 5, 9}


@pytest.mark.parametrize("fmt_name", QUANT_FORMATS)
def test_quantized_paged_write_incremental_decode_stability(fmt_name):
    """Token-by-token decode writes into one page (the serving access
    pattern): every previously written token stays within the round-trip
    bound of its original value after all the requantizations."""
    fmt = quant.resolve(fmt_name)
    rng = np.random.default_rng(3)
    P, ps, K, D = 4, 8, 2, 4
    table = jnp.asarray([[1, 3]], jnp.int32)
    pages = jnp.zeros((P, K, ps, D), fmt.storage_dtype())
    scales = jnp.full((P, K), qops.SCALE_FLOOR, jnp.float32)
    written = []
    for pos in range(2 * ps):
        val = rng.normal(size=(1, 1, K, D)).astype(np.float32)
        written.append(val[0, 0])
        pages, scales = qops.quantized_paged_write(
            pages, scales, jnp.asarray(val), table,
            jnp.asarray([[pos]], jnp.int32), jnp.asarray([1], jnp.int32),
            page_size=ps, fmt=fmt)
    deq = np.asarray(qops.dequantize(
        pages, np.asarray(scales)[:, :, None, None]))
    sc = np.asarray(scales)
    for pos, val in enumerate(written):
        phys = int(table[0, pos // ps])
        got = deq[phys, :, pos % ps]
        bound = _roundtrip_bound(val, sc[phys].max(), fmt)
        # a couple of requantizations may stack: allow 2x the one-shot
        # bound, still far below bf16 storage error for these magnitudes
        assert (np.abs(got - val) <= 2 * bound + 1e-6).all(), pos


@pytest.mark.parametrize("fmt_name", QUANT_FORMATS)
def test_quantized_paged_write_ignores_stale_prior_tenant(fmt_name):
    """retire() frees pages without clearing the device pool, so a
    reused page still holds the previous request's values at positions
    the new tenant hasn't written.  Those rows are unreachable (attention
    masks by position) — they must be zeroed out of the fresh amax, or a
    prior tenant's outliers would crush the new tenant's precision."""
    fmt = quant.resolve(fmt_name)
    P, ps, K, D = 4, 8, 2, 4
    # previous tenant left huge values (amax ~50) across page 1
    stale = jnp.full((K, ps, D), 50.0, jnp.float32)
    s_stale = qops.amax_scale(stale, fmt, axes=(1, 2))
    pages = jnp.zeros((P, K, ps, D), fmt.storage_dtype())
    pages = pages.at[1].set(qops.quantize(stale, s_stale[:, None, None],
                                          fmt))
    scales = jnp.full((P, K), qops.SCALE_FLOOR, jnp.float32)
    scales = scales.at[1].set(s_stale)
    # new tenant (amax ~0.5) writes its first token into the reused page
    table = jnp.asarray([[1]], jnp.int32)
    val = jnp.full((1, 1, K, D), 0.5, jnp.bfloat16)
    new_p, new_s = qops.quantized_paged_write(
        pages, scales, val, table, jnp.asarray([[0]], jnp.int32),
        jnp.asarray([1], jnp.int32), page_size=ps, fmt=fmt)
    # the fresh scale reflects ONLY the live row, not the stale 50s...
    assert float(np.asarray(new_s)[1].max()) <= 0.5 / fmt.fmax * 1.01
    deq = np.asarray(qops.dequantize(new_p,
                                     np.asarray(new_s)[:, :, None, None]))
    # ...so the live row round-trips accurately and the unreachable
    # rows are now exact zeros instead of the prior tenant's values
    assert np.abs(deq[1, :, 0] - 0.5).max() <= float(
        _roundtrip_bound(np.float32(0.5), float(np.asarray(new_s)[1].max()),
                         fmt)) + 1e-6
    assert (deq[1, :, 1:] == 0).all()


def test_quantized_paged_write_drops_sentinel_and_idle():
    fmt = quant.I8
    P, ps, K, D = 3, 4, 1, 2
    table = jnp.asarray([[P, P]], jnp.int32)        # nothing allocated
    pages = jnp.zeros((P, K, ps, D), jnp.int8)
    scales = jnp.zeros((P, K), jnp.float32)
    new_p, new_s = qops.quantized_paged_write(
        pages, scales, jnp.ones((1, 2, K, D), jnp.bfloat16), table,
        jnp.asarray([[0, 1]], jnp.int32), jnp.asarray([2], jnp.int32),
        page_size=ps, fmt=fmt)
    np.testing.assert_array_equal(np.asarray(new_p), np.asarray(pages))
    np.testing.assert_array_equal(np.asarray(new_s), np.asarray(scales))


# --------------------------------------------------------------------------
# policy kv= component
# --------------------------------------------------------------------------

def test_policy_parse_kv_component():
    p = mpx.Policy.parse("p=f32,c=bf16,o=bf16,kv=i8")
    assert p.kv_dtype == "i8"
    assert p.compute_dtype == jnp.bfloat16
    # canonicalized through the quant alias table
    assert mpx.Policy.parse("p=f32,c=bf16,o=f32,kv=int8").kv_dtype == "i8"
    assert mpx.Policy.parse("p=f32,c=bf16,o=f32,kv=fp8").kv_dtype \
        == "f8_e4m3"
    # default stays bf16 and pre-quant policy strings round-trip unchanged
    assert mpx.MIXED_BF16.kv_dtype == "bf16"
    assert "kv=" not in str(mpx.MIXED_BF16)
    assert str(p).endswith(",kv=i8")
    assert mpx.Policy.parse(str(p)) == p
    with pytest.raises(ValueError, match="unknown KV format"):
        mpx.Policy.parse("p=f32,c=bf16,o=f32,kv=i4")


# --------------------------------------------------------------------------
# serving-bench artifact schema (fast — imports the module, runs nothing)
# --------------------------------------------------------------------------

def _load_serving_bench():
    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    import importlib
    return importlib.import_module("benchmarks.serving_bench")


def test_serving_bench_artifact_schema_pinned():
    """The CI-uploaded trajectory keys on these row names: renames must
    update this pin deliberately, never silently."""
    sb = _load_serving_bench()
    names = sb.expected_row_names()
    assert len(names) == len(set(names))
    for required in [
        "serving_hbm_bytes_decode_kvbf16",
        "serving_hbm_bytes_decode_kvi8",
        "serving_hbm_bytes_decode_kvf8",
        "serving_tok_kvbf16", "serving_tok_kvi8", "serving_tok_kvf8",
        "serving_hbm_bytes_decode_gather", "serving_hbm_bytes_decode_paged",
        "serving_spec_accept_rate", "serving_spec_tokens_per_step",
    ]:
        assert required in names, required
    # check_rows accepts exactly the schema and rejects any drift
    rows = [(n, 1.0, "") for n in names]
    sb.check_rows(rows)
    with pytest.raises(RuntimeError, match="drifted"):
        sb.check_rows(rows[:-1])
    renamed = [("serving_tok_kv_i8" if n == "serving_tok_kvi8" else n,
                1.0, "") for n in names]
    with pytest.raises(RuntimeError, match="drifted"):
        sb.check_rows(renamed)


def test_serving_bench_kv_hbm_model_hits_acceptance_ratio():
    """ACCEPTANCE: serving_hbm_bytes_decode_kvi8 <= ~0.55x of the bf16 row
    at the bench shapes (int8 pools + fp32 scale sidecar)."""
    sb = _load_serving_bench()
    cfg = sb._bench_cfg()
    mean_len = 20.0
    bf16 = sb._hbm_bytes_per_decode_token_kv(cfg, mean_len, sb.CMP_PAGE,
                                             quant.BF16)
    i8 = sb._hbm_bytes_per_decode_token_kv(cfg, mean_len, sb.CMP_PAGE,
                                           quant.I8)
    f8 = sb._hbm_bytes_per_decode_token_kv(cfg, mean_len, sb.CMP_PAGE,
                                           quant.F8_E4M3)
    assert i8 / bf16 <= 0.55
    assert f8 / bf16 <= 0.55
    assert i8 / bf16 > 0.5          # the sidecar is accounted, not free
