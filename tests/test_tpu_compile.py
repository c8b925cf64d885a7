"""The paged-attention kernel compiles for a TPU v5e at published widths.

Compiles (never runs) ``repro.kernels.paged_attention`` for a described
``v5e:2x2`` topology at starcoder2-3b's attention widths (24 query heads,
2 kv heads, head_dim 128, page_size 16, 1024 pages) — in bf16 and in every
quantized KV format the serving engine accepts on TPU — and checks that
the formats the engine refuses there still fail to compile.  Interpret
mode cannot see the TPU compiler's tiling rules; this does.  It also
compiles one mamba2-130m SSD block's forward and backward at training
shape and bounds its temporary memory.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import quant
from repro.kernels.paged_attention import paged_attention

H, KV, D, PAGE, N_PAGES = 24, 2, 128, 16, 1024
SLOTS, PMAX = 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the persistent
    # cache without the chip: keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(one_chip, fmt_name, c):
    fmt = quant.resolve(fmt_name)
    pool_dtype = fmt.grid_dtype if fmt.quantized else jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((SLOTS, c, H, D), jnp.bfloat16),
            sds((N_PAGES, KV, PAGE, D), pool_dtype),
            sds((N_PAGES, KV, PAGE, D), pool_dtype),
            sds((SLOTS, PMAX), jnp.int32), sds((SLOTS,), jnp.int32),
            sds((SLOTS,), jnp.int32)]
    if fmt.quantized:
        args += [sds((N_PAGES, KV), jnp.float32)] * 2

    def step(q, k, v, table, start, valid, *scales):
        kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
        return paged_attention(q, k, v, table, start, valid, **kw)

    return jax.jit(step).lower(*args).compile()


SERVED = sorted(set(quant.FORMATS) - quant.TPU_UNSUPPORTED)


@pytest.mark.parametrize("c", [1, 32])
@pytest.mark.parametrize("fmt_name", SERVED)
def test_paged_kernel_compiles_for_v5e(one_chip, fmt_name, c):
    compiled = _compile(one_chip, fmt_name, c)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fmt_name", sorted(quant.TPU_UNSUPPORTED))
def test_refused_formats_do_not_compile_for_v5e(one_chip, fmt_name):
    """The engine's TPU refusal list stays honest: drop a format from it
    once its kernel compiles."""
    with pytest.raises(Exception, match="Mosaic"):
        _compile(one_chip, fmt_name, 1)


def test_paged_kernel_keeps_its_name_and_scope(one_chip):
    """A TPU trace names an op event by its HLO text: the kernel shows as
    ``%paged_attention.<n> = ... custom-call(...)``, tagged with the
    ``attention`` scope it runs in."""
    from repro.obs import scope
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    args = [sds((SLOTS, 1, H, D), jnp.bfloat16),
            sds((N_PAGES, KV, PAGE, D), jnp.bfloat16),
            sds((N_PAGES, KV, PAGE, D), jnp.bfloat16),
            sds((SLOTS, PMAX), jnp.int32), sds((SLOTS,), jnp.int32),
            sds((SLOTS,), jnp.int32)]

    def step(*a):
        with scope("attention"):
            return paged_attention(*a)

    text = jax.jit(step).lower(*args).compile().as_text()
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln
               and " = " in ln]
    assert kernels
    for ln in kernels:
        assert ln.lstrip().startswith("%paged_attention")
        assert 'repro_scope="attention"' in ln


# mamba2-130m's SSD block at published widths, one chip's training rows
SSD_B, SSD_S, SSD_D = 8, 2048, 768
SSD_H, SSD_P, SSD_N, SSD_Q = 24, 64, 128, 256
# temporaries of one block's forward and backward, compiled for a described
# v5e: 615,583,232 bytes with the chunk-parallel scan (the chunk-serial scan
# it replaced: 1,528,395,776)
SSD_BLOCK_TEMP_BYTES = 700_000_000


def test_ssd_block_memory_at_published_widths(one_chip):
    """One mamba2-130m SSD block's forward and backward (8 x 2048, bf16
    compute) compiles for a v5e and its temporaries stay bounded: the
    chunk-parallel scan's largest tensor is a layer's (B, H, nc, Q, Q)
    decay, which must not be kept for every chunk and head at once in
    float32 beside everything else."""
    from repro.nn import param, ssd
    spec = ssd.ssd_spec(SSD_D, SSD_H * SSD_P, SSD_H, SSD_P, SSD_N)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        param.abstract(spec))
    xin = jax.ShapeDtypeStruct((SSD_B, SSD_S, SSD_D), jnp.bfloat16,
                               sharding=one_chip)

    def loss(p, x):
        y = ssd.ssd_block_apply(p, x, n_heads=SSD_H, headdim=SSD_P,
                                d_state=SSD_N, chunk=SSD_Q)
        return jnp.sum(y.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, xin).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert 0 < temp < SSD_BLOCK_TEMP_BYTES, temp


# Nemotron 3 Nano's expert block and attention at published widths, one
# chip's share: 2 x 8192 tokens, 8 of 128 experts held, GQA 32/2
NEM_T, NEM_D, NEM_F, NEM_E, NEM_HELD = 2 * 8192, 2688, 1856, 128, 8


def _kernel_lines(text: str) -> list:
    return [ln.strip() for ln in text.splitlines()
            if "tpu_custom_call" in ln and " = " in ln]


def test_held_expert_block_compiles_with_grouped_matmuls(one_chip,
                                                         monkeypatch):
    """The held-share expert block's forward and backward compile for a
    v5e with the megablox kernels (gmm forward and input gradient, tgmm
    weight gradient) under ``moe``, and its routing under ``moe_route``."""
    from jax.experimental.pallas.ops.tpu.megablox import common
    from repro.nn import moe, param
    from repro.obs import scope
    monkeypatch.setattr(moe, "_interpret", lambda: False)
    monkeypatch.setattr(common, "supports_bfloat16_matmul", lambda: True)
    spec = moe.held_experts_spec(NEM_D, NEM_F, NEM_E, NEM_HELD, 2 * NEM_F)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        param.abstract(spec))
    x = jax.ShapeDtypeStruct((2, NEM_T // 2, NEM_D), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        with scope("moe"):
            y, _ = moe.held_experts_apply(p, x, n_routed=NEM_E, top_k=6,
                                          held=(0, NEM_HELD),
                                          routed_scale=2.5)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = _kernel_lines(text)
    names = {ln[1:].split(" ", 1)[0].rsplit(".", 1)[0] for ln in kernels}
    assert names == {"gmm", "tgmm"}, names
    assert all('repro_scope="moe"' in ln for ln in kernels)
    assert 'repro_scope="moe_route"' in text


def test_splash_attention_compiles_at_8k(one_chip, monkeypatch):
    """Training attention at 8,192 positions takes the splash kernels
    (forward, dq, dkv) on a TPU, tagged ``attention``."""
    from repro.nn import attention, param
    from repro.obs import scope
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    spec = attention.attention_spec(NEM_D, 32, 2, 128)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        param.abstract(spec))
    x = jax.ShapeDtypeStruct((2, 8192, NEM_D), jnp.bfloat16,
                             sharding=one_chip)

    def loss(p, x):
        with scope("attention"):
            y = attention.attention_apply(p, x, n_heads=32, causal=True,
                                          window=0, cap=0.0, rope_theta=0.0)
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = _kernel_lines(text)
    names = {ln.split(" ", 1)[0].rsplit(".", 1)[0] for ln in kernels}
    assert names == {"%splash_mha_fwd_residuals",
                     "%splash_mha_dq_no_residuals",
                     "%splash_mha_dkv_no_residuals"}, names
    # the kernels' own metadata replaces the scope tag; the cell's readers
    # count them under attention by name
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import kernel_time
    assert {kernel_time.scope_of(ln) for ln in kernels} == {"attention"}
