"""Device scopes and program spans (``repro.obs.trace``).

- the compiled train step carries each scope as the ``repro_scope``
  frontend attribute, which a TPU trace prints in each op's name: the SSD
  stack's ``embed`` / ``layers`` / ``ssd`` / ``unembed_loss`` / ``mpx`` /
  ``optimizer``, an attention stack's ``attention`` and ``mlp``;
- the serving engine's compiled step carries its block scopes and
  ``sampling``;
- on a CPU ``jax.profiler`` trace, ``Trainer.fit`` puts each step in a
  ``train`` step annotation holding ``data wait`` and ``host sync``, and
  ``ServeEngine.step`` opens its five phases inside a ``serve`` step.
"""
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from repro import mpx, serve
from repro.configs import registry
from repro.configs.base import ModelConfig, RunConfig
from repro.data.pipeline import SyntheticTokens
from repro.models import transformer as T
from repro.obs import PhaseSpans, program_span, scope
from repro.obs.trace import SCOPE_ATTR, SCOPES
from repro.optim import make_optimizer
from repro.train import state as S
from repro.train.steps import make_train_step
from repro.train.trainer import Trainer, TrainerConfig

ATTN_CFG = ModelConfig(
    name="scope-test", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256, pattern=("attn",), mlp="swiglu",
    tie_embeddings=True, remat="full",
)
MIXED = RunConfig(policy="params=float32,compute=bfloat16,output=float32",
                  loss_scaling="dynamic", grad_clip=1.0)


def _scopes(text: str) -> set:
    return set(re.findall(SCOPE_ATTR + r'="([^"]*)"', text))


def _compiled_train_step(cfg, batch=2, seq=32) -> str:
    opt = make_optimizer(MIXED)
    state = jax.eval_shape(
        lambda: S.init_state(jax.random.key(0), cfg, MIXED, opt))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    step = jax.jit(make_train_step(cfg, MIXED, opt))
    return step.lower(state, {"inputs": tokens,
                              "targets": tokens}).compile().as_text()


@pytest.mark.parametrize("cfg, expected", [
    (registry.get_smoke_config("mamba2-130m"),
     {"embed", "layers", "ssd", "unembed_loss", "mpx", "optimizer"}),
    (ATTN_CFG, {"embed", "layers", "attention", "mlp", "unembed_loss",
                "mpx", "optimizer"}),
    (registry.get_smoke_config("nemotron-3-nano-30b-a3b"),
     {"embed", "layers", "ssd", "attention", "moe", "moe_route",
      "unembed_loss", "mpx", "optimizer"}),
], ids=["ssd", "attention", "hybrid"])
def test_compiled_train_step_carries_scopes(cfg, expected):
    found = _scopes(_compiled_train_step(cfg))
    assert expected <= found, expected - found
    assert found <= set(SCOPES)


def _capture_device_step(engine) -> str:
    """Run one tick, keeping the compiled step's argument shapes."""
    seen = []
    real = engine._device_step

    def spy(*args):
        seen.append(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
        return real(*args)

    engine._device_step = spy
    engine.submit([1, 2, 3, 4, 5], max_new=2)
    engine.step()
    engine._device_step = real
    return real.lower(*seen[0]).compile().as_text()


@pytest.mark.serve
def test_compiled_serve_step_carries_block_scopes_and_sampling():
    params = mpx.cast_to_bfloat16(T.init_params(jax.random.key(0),
                                                ATTN_CFG))
    engine = serve.ServeEngine(ATTN_CFG, params, n_slots=2, max_seq=64,
                               page_size=16, chunk_size=16)
    found = _scopes(_capture_device_step(engine))
    assert {"embed", "layers", "attention", "mlp", "unembed_loss",
            "sampling"} <= found, found


def test_scope_rejects_unknown_names():
    with pytest.raises(ValueError, match="unknown scope"):
        with scope("attn"):
            pass
    with pytest.raises(ValueError, match="unknown program span"):
        program_span("tick")


def test_innermost_scope_wins():
    def f(x):
        with scope("layers"):
            y = x * 2.0
            with scope("ssd"):
                return jnp.sin(y)

    text = jax.jit(f).lower(jnp.ones(4)).as_text()
    sin = [ln for ln in text.splitlines() if "stablehlo.sine" in ln]
    mul = [ln for ln in text.splitlines() if "stablehlo.multiply" in ln]
    assert sin and all('repro_scope = "ssd"' in ln for ln in sin)
    assert mul and all('repro_scope = "layers"' in ln for ln in mul)


def test_phase_spans_close_on_exception():
    with pytest.raises(RuntimeError):
        with PhaseSpans() as phase:
            phase.start("admit")
            phase.start("plan")
            raise RuntimeError("boom")
    assert phase._open is None


# --------------------------------------------------------------------------
# program spans on a CPU profiler trace
# --------------------------------------------------------------------------

def _host_events(trace_dir) -> list:
    """(name, start_ns, end_ns) of every host-plane event of the trace."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
    return out


def _traced(fn, trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_events(trace_dir)


def _inside(events, name, outer) -> list:
    """Events named ``name`` that lie within some ``outer`` event."""
    outers = [(s, e) for n, s, e in events if n == outer]
    return [(s, e) for n, s, e in events if n == name
            and any(os_ <= s and e <= oe for os_, oe in outers)]


def test_trainer_steps_show_train_annotations_and_data_wait(tmp_path):
    cfg = registry.get_smoke_config("llama3-8b")
    trainer = Trainer(cfg, MIXED, make_optimizer(MIXED),
                      SyntheticTokens(cfg, batch=2, seq=16, seed=0),
                      TrainerConfig(total_steps=2, log_every=1, prefetch=2,
                                    ckpt_dir=str(tmp_path / "ckpt"),
                                    ckpt_every=1))
    events = _traced(trainer.fit, tmp_path / "trace")
    assert sum(n == "train" for n, _, _ in events) == 2
    for span in ("data wait", "host sync", "checkpoint"):
        assert len(_inside(events, span, "train")) == 2, span


@pytest.mark.serve
def test_engine_step_shows_its_phases(tmp_path):
    params = mpx.cast_to_bfloat16(T.init_params(jax.random.key(0),
                                                ATTN_CFG))
    engine = serve.ServeEngine(ATTN_CFG, params, n_slots=2, max_seq=64,
                               page_size=16, chunk_size=16)
    engine.submit([1, 2, 3], max_new=2)
    engine.step()                                    # compile outside
    engine.submit([4, 5, 6, 7], max_new=2)
    events = _traced(engine.step, tmp_path)
    assert sum(n == "serve" for n, _, _ in events) == 1
    for phase in ("admit", "plan", "device step", "host sync", "commit"):
        assert len(_inside(events, phase, "serve")) == 1, phase
