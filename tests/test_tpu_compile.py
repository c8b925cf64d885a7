"""The paged-attention kernel compiles for a TPU v5e at published widths.

Compiles (never runs) ``repro.kernels.paged_attention`` for a described
``v5e:2x2`` topology at starcoder2-3b's attention widths (24 query heads,
2 kv heads, head_dim 128, page_size 16, 1024 pages) — in bf16 and in every
quantized KV format the serving engine accepts on TPU — and checks that
the formats the engine refuses there still fail to compile.  Interpret
mode cannot see the TPU compiler's tiling rules; this does.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library.
"""
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
