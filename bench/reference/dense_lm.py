"""Plain float32 forward of a dense GQA transformer: StarCoder2.

Pre-LayerNorm blocks with biases, rotary embeddings (rotate-half),
grouped-query causal attention and a tanh-GELU MLP, tied unembedding
(Lozhkov et al. 2024, arXiv:2402.19173; the HF ``Starcoder2`` model).
The model runs layer by layer, one jitted layer at a time, over a block
of sequences at once (at most ``TOKENS_PER_BLOCK`` padded positions), so
that it fits beside the bfloat16 weights it reads: each layer's weights
are upcast to float32 when it runs.  Attention is computed in blocks of
queries.  Sequences are padded at the end to a multiple of the query
block, and a block's rows to a power of two with empty sequences;
causal masking keeps the padding out of every real position.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ops

Q_BLOCK = 512
TOKENS_PER_BLOCK = 24576


def _padded(length: int) -> int:
    return max(Q_BLOCK, -(-length // Q_BLOCK) * Q_BLOCK)


def _rows(count: int, n: int) -> int:
    """Rows a block of ``count`` sequences of padded length ``n`` runs
    as: the next power of two, within ``TOKENS_PER_BLOCK``, so that few
    shapes compile."""
    return min(1 << (count - 1).bit_length(), max(1, TOKENS_PER_BLOCK // n))


def _blocks(seqs) -> list:
    """Groups of sequence indices, longest first, whose count times
    their longest padded length stays within ``TOKENS_PER_BLOCK``."""
    out, cur, n = [], [], 0
    for i in sorted(range(len(seqs)), key=lambda i: -len(seqs[i])):
        n_i = _padded(len(seqs[i]))
        if cur and (len(cur) + 1) * max(n, n_i) > TOKENS_PER_BLOCK:
            out.append(cur)
            cur, n = [], 0
        cur.append(i)
        n = max(n, n_i)
    return out + [cur] if cur else out


def _rope(x, theta: float):
    """Rotate-half RoPE over positions 0..N-1; x (S, N, heads, D)."""
    n, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, *, n_heads: int, n_kv: int, theta: float, eps: float,
           fp8: bool):
    """One block over x (S, N, d) float32."""
    w = ops.f32(w)
    s_, n, _ = x.shape
    e = functools.partial(ops.einsum, fp8_operands=fp8)
    h = ops.layer_norm(x, w["pre_norm"]["scale"], w["pre_norm"]["bias"], eps)
    a = w["attn"]
    q = _rope(e("snd,dhk->snhk", h, a["wq"]) + a["bq"], theta)
    k = _rope(e("snd,dhk->snhk", h, a["wk"]) + a["bk"], theta)
    v = e("snd,dhk->snhk", h, a["wv"]) + a["bv"]
    d = q.shape[-1]
    g = n_heads // n_kv
    nb = n // Q_BLOCK
    qb = q.reshape(s_, nb, Q_BLOCK, n_kv, g, d).transpose(1, 0, 2, 3, 4, 5)

    def block(args):
        i, qq = args
        sc = jnp.einsum("sqkgd,snkd->skgqn", qq, k,
                        precision=ops.HIGHEST) / math.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        ok = jnp.arange(n)[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("skgqn,snkd->sqkgd", p, v, precision=ops.HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb), qb))
    o = o.transpose(1, 0, 2, 3, 4, 5).reshape(s_, n, n_heads, d)
    x = x + e("snhk,hkd->snd", o, a["wo"]) + a["bo"]
    h = ops.layer_norm(x, w["mlp_norm"]["scale"], w["mlp_norm"]["bias"], eps)
    mlp = w["mlp"]
    u = jax.nn.gelu(e("snd,df->snf", h, mlp["w_up"]) + mlp["b_up"],
                    approximate=True)
    return x + e("snf,fd->snd", u, mlp["w_down"]) + mlp["b_down"]


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, final, tok, rows, cols, *, eps: float, fp8: bool):
    xs = ops.layer_norm(x[rows, cols], final["scale"].astype(jnp.float32),
                        final["bias"].astype(jnp.float32), eps)
    return ops.einsum("pd,vd->pv", xs, tok, fp8_operands=fp8)


def logits_at(weights, config: dict, seqs, positions, *, fp8: bool = False):
    """float32 logits of each sequence of ``seqs`` (token-id lists) at
    its ``positions``; returns one (len(positions[i]), V) array each."""
    if set(weights) != {"embed", "scan", "final_norm"} or \
            set(weights["scan"]) != {"b0"}:
        raise ValueError("expected a scanned single-kind layer stack")
    m, pub = config["model"], config["published"]
    layer = jax.jit(functools.partial(
        _layer, n_heads=m["n_heads"], n_kv=m["n_kv_heads"],
        theta=float(m["rope_theta"]), eps=float(pub["norm_epsilon"]),
        fp8=fp8))
    out = [None] * len(seqs)
    for block in _blocks(seqs):
        logits = _block_logits(weights, config, layer,
                               [seqs[i] for i in block],
                               [positions[i] for i in block], fp8)
        for i, lg in zip(block, logits):
            out[i] = lg
    return out


def _block_logits(weights, config: dict, layer, seqs, positions,
                  fp8: bool):
    m, pub = config["model"], config["published"]
    n = _padded(max(len(s) for s in seqs))
    ids = np.zeros((_rows(len(seqs), n), n), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    tok = weights["embed"]["tok"]
    x = jnp.take(tok, jnp.asarray(ids), axis=0).astype(jnp.float32)
    stack = weights["scan"]["b0"]
    for li in range(m["n_layers"]):
        x = layer(x, jax.tree.map(lambda a, li=li: a[li], stack))
    rows = np.concatenate([np.full(len(p), i) for i, p in enumerate(positions)])
    cols = np.concatenate([np.asarray(p) for p in positions])
    total = len(rows)
    pad = -total % 128                       # few distinct head shapes
    rows, cols = np.pad(rows, (0, pad)), np.pad(cols, (0, pad))
    out = np.asarray(_head(x, weights["final_norm"], tok, jnp.asarray(rows),
                           jnp.asarray(cols), eps=float(pub["norm_epsilon"]),
                           fp8=fp8))[:total]
    splits = np.cumsum([len(p) for p in positions])[:-1]
    return np.split(out, splits)
