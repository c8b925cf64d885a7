"""Nemotron 3 Nano (hybrid Mamba-2 / expert-only / GQA blocks) against the
plain float32 reference ``bench/reference/nemotron_h.py`` at a tiny size.

- the program's train step in float32 compute gives the reference's loss,
  first gradient and parameters after three AdamW steps;
- the grouped SSD block (8 groups) gives the reference's Mamba-2 mixer,
  and one group passed as a (B, S, 1, N) array gives the ungrouped scan;
- the held-share expert layer: the shares of 2 or 4 parts, with the
  shared expert counted once, add up to the uncut reference's layer, and
  no assignment to a held expert is dropped when every token picks the
  same experts;
- the full configuration counts 31.6 B parameters, and serving refuses
  the expert-only kind.
"""
import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import weights  # noqa: E402
from bench.reference import nemotron_h as ref  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.configs.base import RunConfig  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.nn import moe, param, ssd  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.train import state as S  # noqa: E402
from repro.train.steps import make_train_step  # noqa: E402

ARCH = "nemotron-3-nano-30b-a3b"
EPS = 1e-5          # the published norm eps, the program's and the reference's
FP32 = RunConfig(policy="params=float32,compute=float32,output=float32",
                 loss_scaling="none", learning_rate=3e-4, weight_decay=0.1,
                 grad_clip=1.0, moe_aux_weight=0.0, zero1=False)


def _cfg(**kw):
    return dataclasses.replace(registry.get_smoke_config(ARCH), **kw)


def _model_dict(cfg) -> dict:
    """The configuration file's ``model`` entry for ``cfg``."""
    keys = ("pattern", "n_layers", "ssm_n_heads", "ssm_groups", "moe_top_k",
            "moe_held", "moe_routed_scale")
    return {k: getattr(cfg, k) for k in keys}


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _batches(cfg, n, batch=2, seq=32, seed=3):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32),
             rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32))
            for _ in range(n)]


def test_program_matches_reference_loss_grad_and_adamw():
    cfg = _cfg()
    params = weights.make(T.param_shapes(cfg), 7, jnp.float32)
    batches = _batches(cfg, 3)
    opt = make_optimizer(FP32)
    state = S.init_state(jax.random.key(0), cfg, FP32, opt)
    state["params"] = params
    step = jax.jit(make_train_step(cfg, FP32, opt))
    losses, first, norm = [], None, None
    for inputs, targets in batches:
        state, met = step(state, {"inputs": inputs, "targets": targets})
        losses.append(float(met["loss"]))
        if first is None:
            first = jax.tree.map(lambda m: m / (1 - FP32.beta1),
                                 state["opt_state"]["mu"])
            norm = float(met["grad_norm"])
    config = {"model": _model_dict(cfg), "published": {"norm_eps": EPS}}
    hp = dataclasses.asdict(FP32)
    want = ref.adamw_steps(params, batches, config, hp)
    # AdamW divides by sqrt(v) + 1e-8: an element whose gradient is at
    # round-off (~1e-8) moves by about the learning rate in the direction
    # round-off gives it, on either side.  So the loss before any update is
    # compared tightly, the later ones and the change by leaf norms, as the
    # benchmark's check compares them.
    np.testing.assert_allclose(losses[0], want["losses"][0], rtol=1e-6)
    np.testing.assert_allclose(losses, want["losses"], rtol=1e-3)
    assert abs(norm - want["first_grad_norm"]) / want["first_grad_norm"] \
        < 1e-4
    flat = jax.tree_util.tree_flatten_with_path(want["first_grad"])[0]
    got = dict(jax.tree_util.tree_flatten_with_path(first)[0])
    for path, g in flat:
        assert _rel(got[path], g) < 2e-3, jax.tree_util.keystr(path)

    def norms(tree):
        moved = jax.tree.map(jnp.subtract, tree, params)
        return {jax.tree_util.keystr(k): float(np.linalg.norm(v))
                for k, v in jax.tree_util.tree_flatten_with_path(moved)[0]}

    moved, want_moved = norms(state["params"]), norms(want["params"])
    med = np.median(list(want_moved.values()))
    for name, b in want_moved.items():
        assert abs(moved[name] - b) / max(b, med) < 2e-3, name


def _ssd_params(heads, headdim, groups, n, d, seed=1):
    spec = ssd.ssd_spec(d, heads * headdim, heads, headdim, n, 4, groups)
    return weights.make(param.abstract(spec), seed, jnp.float32)


@pytest.mark.parametrize("seq", [32, 44])
def test_grouped_ssd_block_matches_reference_mixer(seq):
    heads, headdim, groups, n, d = 16, 8, 8, 12, 40
    p = _ssd_params(heads, headdim, groups, n, d)
    x = jax.random.normal(jax.random.key(2), (2, seq, d), jnp.float32)

    def prog(p, x):
        return ssd.ssd_block_apply(p, x, n_heads=heads, headdim=headdim,
                                   d_state=n, chunk=16, n_groups=groups,
                                   eps=EPS)

    def want(p, x):
        return jax.vmap(lambda r: ref._ssd(r, p, heads=heads, groups=groups,
                                           eps=EPS, fp8=False))(x)

    with jax.default_matmul_precision("highest"):
        assert _rel(prog(p, x), want(p, x)) < 1e-4
        cot = jax.random.normal(jax.random.key(4), (2, seq, d))
        g_prog = jax.grad(lambda p, x: jnp.sum(prog(p, x) * cot),
                          argnums=(0, 1))(p, x)
        g_want = jax.grad(lambda p, x: jnp.sum(want(p, x) * cot),
                          argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_want)):
        assert _rel(a, b) < 1e-3


def test_one_group_is_the_ungrouped_scan():
    k = jax.random.split(jax.random.key(0), 6)
    b, s, h, p, n = 2, 40, 4, 8, 6
    x = jax.random.normal(k[0], (b, s, h, p))
    dt = jax.random.uniform(k[1], (b, s, h), minval=0.01, maxval=0.3)
    a = -jax.random.uniform(k[2], (h,), minval=0.5, maxval=4.0)
    bm = jax.random.normal(k[3], (b, s, n))
    cm = jax.random.normal(k[4], (b, s, n))
    d_skip = jax.random.normal(k[5], (h,))
    flat = ssd.ssd_chunked(x, dt, a, bm, cm, d_skip, 16)
    grouped = ssd.ssd_chunked(x, dt, a, bm[:, :, None], cm[:, :, None],
                              d_skip, 16)
    assert _rel(grouped, flat) < 1e-6


def _expert_layer(n_routed=16, top_k=4, d=32, f=24, fs=48, seed=5):
    spec = moe.held_experts_spec(d, f, n_routed, n_routed, fs)
    return weights.make(param.abstract(spec), seed, jnp.float32)


def _share(p, first, count):
    """The parameters a layer holding experts [first, first+count) has."""
    return dict(p, w_up=p["w_up"][:, first:first + count],
                w_down=p["w_down"][:, first:first + count])


@pytest.mark.parametrize("parts", [2, 4])
def test_held_shares_add_up_to_the_whole_layer(parts):
    n_routed, top_k = 16, 4
    p = _expert_layer(n_routed, top_k)
    h = jax.random.normal(jax.random.key(6), (2, 24, 32))
    count = n_routed // parts
    total, assigned = 0.0, 0.0
    for i in range(parts):
        y, stats = moe.held_experts_apply(
            _share(p, i * count, count), h, n_routed=n_routed, top_k=top_k,
            held=(i * count, count), routed_scale=2.5)
        total = total + y
        assigned += float(stats["moe_held_assignments"])

    def layer(held):
        return jnp.stack([ref._experts(r, held, top_k=top_k, first=0,
                                       scale=2.5, fp8=False) for r in h])

    with jax.default_matmul_precision("highest"):
        # every share adds the shared expert: count it once
        shared = jnp.square(jax.nn.relu(h @ p["shared_up"])) \
            @ p["shared_down"]
        assert _rel(total - (parts - 1) * shared, layer(p)) < 1e-5
    assert assigned == h.shape[0] * h.shape[1] * top_k


def test_skewed_routing_drops_no_held_assignment():
    """Every token picks the same top-k experts, all held: the buffer
    takes every assignment and the output is the reference's."""
    n_routed, top_k, d = 16, 4, 32
    p = _expert_layer(n_routed, top_k, d=d)
    favour = jnp.zeros((d, n_routed)).at[:, 2:2 + top_k].set(5.0)
    p = dict(p, router=p["router"] + favour)
    h = jnp.abs(jax.random.normal(jax.random.key(8), (2, 40, d)))
    first, count = 0, 8
    y, stats = moe.held_experts_apply(
        _share(p, first, count), h, n_routed=n_routed, top_k=top_k,
        held=(first, count), routed_scale=2.5)
    want = jnp.stack([ref._experts(r, _share(p, first, count), top_k=top_k,
                                   first=first, scale=2.5, fp8=False)
                      for r in h])
    assert float(stats["moe_held_assignments"]) == 2 * 40 * top_k
    assert float(stats["moe_held_load_max"]) == pytest.approx(count / top_k)
    with jax.default_matmul_precision("highest"):
        assert _rel(y, want) < 1e-5


def test_full_config_has_the_published_parameter_count():
    n = T.count_params(registry.get_config(ARCH))
    assert abs(n - 31.6e9) / 31.6e9 < 0.03, n


def test_serving_refuses_the_expert_only_kind():
    cfg = _cfg()
    assert not cfg.supports_decode()
    with pytest.raises(ValueError, match="'moe'"):
        T.init_paged_cache(cfg, n_pages=8, page_size=4, n_slots=2)


def test_splash_path_matches_the_plain_path(monkeypatch):
    """The TPU training path of attention (the splash kernel, here run by
    the Pallas interpreter) gives attend_plain's output and gradients."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    from repro.nn import attention

    def interpreted(heads, seq):
        mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))] * heads)
        blk = 128
        sizes = sk.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=blk,
                              block_q_dkv=blk, block_kv_dkv=blk,
                              block_kv_dkv_compute=blk, block_q_dq=blk,
                              block_kv_dq=blk)
        return sk.make_splash_mha_single_device(mask, block_sizes=sizes,
                                                interpret=True)

    monkeypatch.setattr(attention, "_splash_kernel", interpreted)
    k = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(k[0], (2, 256, 4, 32))
    kk = jax.random.normal(k[1], (2, 256, 2, 32))
    v = jax.random.normal(k[2], (2, 256, 2, 32))
    cot = jax.random.normal(k[3], (2, 256, 4, 32))

    def loss(fn, q, kk, v):
        return jnp.sum(fn(q, kk, v) * cot)

    plain = lambda q, kk, v: attention.attend_plain(  # noqa: E731
        q, kk, v, causal=True, window=0, cap=0.0)
    with jax.default_matmul_precision("highest"):
        assert _rel(attention.attend_splash(q, kk, v), plain(q, kk, v)) < 1e-5
        got = jax.grad(functools.partial(loss, attention.attend_splash),
                       argnums=(0, 1, 2))(q, kk, v)
        want = jax.grad(functools.partial(loss, plain),
                        argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(got, want):
        assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("seq", [64, 100])
def test_reference_recurrence_is_the_token_serial_one(seq):
    """The reference's block-wise recurrence (its quadratic form within
    blocks of tokens, the state carried between them) against the
    definition, one token at a time, values and gradients."""
    heads, p, n, groups = 4, 3, 5, 2
    k = jax.random.split(jax.random.key(9), 5)
    x = jax.random.normal(k[0], (seq, heads, p))
    dt = jax.random.uniform(k[1], (seq, heads), minval=0.01, maxval=0.3)
    a = -jax.random.uniform(k[2], (heads,), minval=0.5, maxval=4.0)
    b = jax.random.normal(k[3], (seq, groups, n))
    c = jax.random.normal(k[4], (seq, groups, n))

    def serial(x, dt, a, b, c):
        per = heads // groups
        bh, ch = (jnp.repeat(t, per, axis=1) for t in (b, c))

        def step(h, inp):
            xt, dtt, bt, ct = inp
            h = h * jnp.exp(dtt * a)[:, None, None] \
                + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, ct)

        h0 = jnp.zeros((heads, p, n))
        return jax.lax.scan(step, h0, (x, dt, bh, ch))[1]

    block = functools.partial(ref._recurrence, groups=groups)
    with jax.default_matmul_precision("highest"):
        assert _rel(block(x, dt, a, b, c), serial(x, dt, a, b, c)) < 1e-5
        got = jax.grad(lambda *v: jnp.sum(jnp.sin(block(*v))),
                       argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
        want = jax.grad(lambda *v: jnp.sum(jnp.sin(serial(*v))),
                        argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    for g, w in zip(got, want):
        assert _rel(g, w) < 1e-4
