"""Plain float32 Nemotron-H language model (Nemotron 3 Nano): loss,
gradients, AdamW steps, on one chip's share of each expert layer.

The stack is a sequence of blocks ``x + mixer(RMSNorm(x))``, one mixer a
block, of three kinds (the configuration's ``pattern``):

- ``ssd``, Mamba-2 with grouped B and C: projections z, x (H*P wide),
  B and C (G groups of N each) and dt; a causal depthwise conv (width 4,
  no bias) and SiLU on x, B and C; the selective state recurrence, head h
  reading group h // (H/G),

      h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t,

  unrolled within blocks of ``BLOCK`` tokens into its quadratic form,

      y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < u <= t} dt_u A) dt_s x_s
            + exp(sum_{u <= t} dt_u A) h_0 C_t

  (h_0 the state entering the block), and carried from block to block by
  the recurrence; then ``RMSNorm(y * silu(z))`` over each group's H*P/G
  channels, and the output projection (Dao & Gu 2024, arXiv:2405.21060);
- ``moe``, the DeepSeek-V3 expert layer as Nemotron-H configures it:
  sigmoid scores of an fp32 router over all routed experts, the top k of
  score plus a correction bias (zero), weights the picked scores over
  their sum times the routed scale; each held expert
  ``down(relu(up h)^2)`` over every token, weighted by the token's
  weight for it (zero if not picked); plus the shared expert;
- ``attn``, causal GQA without biases or rotary embedding, by blocks of
  queries against every key.

A final RMSNorm and the untied output head give the logits over the
vocabulary slice.  Rows are taken one at a time and every block is
recomputed in the backward pass, as is each block of the recurrence.
None of the program's code is used.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import ops

BLOCK = 64          # tokens of the recurrence unrolled at once
Q_BLOCK = 512       # query rows of attention computed at once


def _conv(x, w):
    """Causal depthwise conv: y_t = sum_i x_{t-W+1+i} w_i; x (S, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(width))


def _recurrence(x, dt, a, b, c, groups: int):
    """y_t = h_t C_t with h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, head
    h reading group h // (H/G).  x (S,H,P), dt (S,H), a (H,), b/c
    (S,G,N) -> (S,H,P).  A zero-padded tail (dt = 0) leaves the state as
    it is."""
    s, heads, p = x.shape
    per = heads // groups
    pad = (-s) % BLOCK
    blocks = lambda v: jnp.pad(  # noqa: E731
        v, ((0, pad),) + ((0, 0),) * (v.ndim - 1)).reshape(
            -1, BLOCK, *v.shape[1:])
    e = functools.partial(jnp.einsum, precision=ops.HIGHEST)
    causal = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))[:, :, None]

    @jax.checkpoint
    def block(h, inp):
        xb, dtb, bb, cb = inp
        bb = jnp.repeat(bb, per, axis=1)                      # (L,H,N)
        cb = jnp.repeat(cb, per, axis=1)
        cum = jnp.cumsum(dtb * a, axis=0)                     # (L,H)
        decay = jnp.exp(jnp.where(causal, cum[:, None] - cum[None, :],
                                  -jnp.inf))                  # (t,s,H)
        y = e("tsh,shp->thp", e("thn,shn->tsh", cb, bb) * decay * dtb,
              xb) + e("thn,hpn->thp", cb, h) * jnp.exp(cum)[:, :, None]
        w = jnp.exp(cum[-1] - cum) * dtb                      # (L,H)
        h = h * jnp.exp(cum[-1])[:, None, None] \
            + e("sh,shp,shn->hpn", w, xb, bb)
        return h, y

    h0 = jnp.zeros((heads, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(block, h0, (blocks(x), blocks(dt), blocks(b),
                                    blocks(c)))
    return y.reshape(-1, heads, p)[:s]


def _ssd(h, s, *, heads: int, groups: int, eps: float, fp8: bool):
    e = functools.partial(ops.einsum, fp8_operands=fp8)
    n_tok = h.shape[0]
    z = e("sd,di->si", h, s["w_z"])
    x = jax.nn.silu(_conv(e("sd,di->si", h, s["w_x"]), s["conv_x"]))
    b = jax.nn.silu(_conv(e("sd,dn->sn", h, s["w_B"]), s["conv_B"]))
    c = jax.nn.silu(_conv(e("sd,dn->sn", h, s["w_C"]), s["conv_C"]))
    dt = jax.nn.softplus(e("sd,dh->sh", h, s["w_dt"]) + s["dt_bias"])
    a = -jnp.exp(s["A_log"])
    xh = x.reshape(n_tok, heads, -1)
    y = _recurrence(xh, dt, a, b.reshape(n_tok, groups, -1),
                    c.reshape(n_tok, groups, -1), groups) \
        + s["D"][None, :, None] * xh
    y = y.reshape(n_tok, -1) * jax.nn.silu(z)
    y = y.reshape(n_tok, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = y.reshape(n_tok, -1) * s["norm_w"]
    return e("si,id->sd", y, s["w_out"])


def _experts(h, m, *, top_k: int, first: int, scale: float, fp8: bool):
    e = functools.partial(ops.einsum, fp8_operands=fp8)
    logits = e("sd,de->se", h, m["router"])
    scores = jax.nn.sigmoid(logits)
    bias = jnp.zeros(scores.shape[-1], jnp.float32)   # correction bias
    _, picked = jax.lax.top_k(scores + bias, top_k)   # (S,k)
    weight = jnp.take_along_axis(scores, picked, axis=-1)
    weight = weight / jnp.sum(weight, -1, keepdims=True) * scale
    held = m["w_up"].shape[1]

    @jax.checkpoint
    def one(out, i):
        mine = jnp.sum(jnp.where(picked == first + i, weight, 0.0), -1)
        up = jnp.take(m["w_up"], i, axis=1)               # (d, f)
        down = jnp.take(m["w_down"], i, axis=1)           # (f, d)
        y = e("sf,fd->sd", jnp.square(jax.nn.relu(e("sd,df->sf", h, up))),
              down)
        return out + mine[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    shared = jnp.square(jax.nn.relu(e("sd,df->sf", h, m["shared_up"])))
    return out + e("sf,fd->sd", shared, m["shared_down"])


def _attention(h, p, *, fp8: bool):
    e = functools.partial(ops.einsum, fp8_operands=fp8)
    q = e("sd,dhk->shk", h, p["wq"])
    k = e("sd,dgk->sgk", h, p["wk"])
    v = e("sd,dgk->sgk", h, p["wv"])
    n_tok, heads, dim = q.shape
    groups = k.shape[1]
    qg = q.reshape(n_tok, groups, heads // groups, dim) / np.sqrt(dim)
    blk = Q_BLOCK if n_tok % Q_BLOCK == 0 else n_tok

    @jax.checkpoint
    def rows(_, i):
        qb = jax.lax.dynamic_slice_in_dim(qg, i * blk, blk)
        scores = e("qgrk,sgk->grqs", qb, k)
        pos = i * blk + jnp.arange(blk)
        scores = jnp.where(jnp.arange(n_tok)[None, :] <= pos[:, None],
                           scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return None, e("grqs,sgk->qgrk", probs, v)

    _, out = jax.lax.scan(rows, None, jnp.arange(n_tok // blk))
    out = out.reshape(n_tok, heads, dim)
    return e("shk,hkd->sd", out, p["wo"])


def _block(x, p, kind: str, *, m: dict, eps: float, fp8: bool):
    h = ops.rms_norm(x, p["pre_norm"]["scale"], eps)
    if kind == "ssd":
        heads = m["ssm_n_heads"]
        y = _ssd(h, p["ssd"], heads=heads, groups=m["ssm_groups"], eps=eps,
                 fp8=fp8)
    elif kind == "moe":
        y = _experts(h, p["moe"], top_k=m["moe_top_k"],
                     first=m["moe_held"][0], scale=m["moe_routed_scale"],
                     fp8=fp8)
    elif kind == "attn":
        y = _attention(h, p["attn"], fp8=fp8)
    else:
        raise ValueError(f"no reference for layer kind {kind!r}")
    return x + y


def row_loss(params, inputs, targets, *, m: dict, eps: float,
             fp8: bool = False):
    """Mean next-token cross-entropy of one row (S,) of token ids."""
    kinds = list(m["pattern"])
    block = {k: jax.checkpoint(functools.partial(_block, kind=k, m=m,
                                                 eps=eps, fp8=fp8))
             for k in set(kinds)}

    def group(x, gp):
        for i, kind in enumerate(kinds):
            x = block[kind](x, gp[f"b{i}"])
        return x, None

    x = params["embed"]["tok"][inputs]
    x, _ = jax.lax.scan(group, x, params["scan"])
    n_tail = m["n_layers"] % len(kinds)
    for j in range(n_tail):
        x = block[kinds[j]](x, params[f"tail{j}"])
    x = ops.rms_norm(x, params["final_norm"]["scale"], eps)
    logits = ops.einsum("sd,dv->sv", x, params["unembed"]["w"],
                        fp8_operands=fp8)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, targets[:, None],
                                              axis=-1)[:, 0])


def _static(model: dict) -> tuple:
    keys = ("pattern", "n_layers", "ssm_n_heads", "ssm_groups", "moe_top_k",
            "moe_held", "moe_routed_scale")
    return tuple((k, tuple(model[k]) if isinstance(model[k], list)
                  else model[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("static", "eps", "fp8"))
def _loss_and_grad(params, inputs, targets, *, static, eps, fp8):
    grad_fn = jax.value_and_grad(functools.partial(
        row_loss, m=dict(static), eps=eps, fp8=fp8))

    def body(carry, row):
        loss, g = grad_fn(params, *row)
        return (carry[0] + loss, jax.tree.map(jnp.add, carry[1], g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros(()), zeros),
                                    (inputs, targets))
    rows = inputs.shape[0]
    return loss / rows, jax.tree.map(lambda g: g / rows, grads)


def loss_and_grad(params, inputs, targets, config: dict, fp8: bool = False):
    """Batch-mean loss and its float32 gradient, one row at a time."""
    return _loss_and_grad(
        ops.f32(params), jnp.asarray(inputs), jnp.asarray(targets),
        static=_static(config["model"]),
        eps=float(config["published"]["norm_eps"]), fp8=fp8)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


@functools.partial(jax.jit, static_argnames=("b1", "b2", "lr", "eps", "wd"))
def _adamw_leaf(w, g, m, v, scale, c1, c2, *, b1, b2, lr, eps, wd):
    """One leaf's AdamW step on the chip, its gradient times ``scale``."""
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    w = w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w)
    return w, m, v


def adamw_steps(params, batches, config: dict, hp: dict, fp8: bool = False):
    """AdamW with global-norm clipping over ``batches``, as ``hp`` (the
    job's optimizer settings) states; weight decay on every leaf.
    Returns the losses, the first step's gradient as the optimizer got
    it (clipped), its pre-clip global norm, and the final parameters.
    The moments and the first gradient are kept on the host and each
    leaf's update runs on the chip in turn, so that the chip holds the
    parameters, one gradient and the activations of one row at a time."""
    p = ops.f32(params)
    b1, b2 = hp["beta1"], hp["beta2"]
    step = functools.partial(_adamw_leaf, b1=b1, b2=b2,
                             lr=hp["learning_rate"], eps=hp.get("eps", 1e-8),
                             wd=hp["weight_decay"])
    mu = nu = first = first_norm = None
    losses = []
    for k, (inputs, targets) in enumerate(batches, start=1):
        loss, g = loss_and_grad(p, inputs, targets, config, fp8)
        norm = global_norm(g)
        scale = jnp.minimum(1.0, hp["grad_clip"] / norm)
        if first is None:
            first = jax.device_get(jax.tree.map(lambda x: x * scale, g))
            first_norm = norm
            mu = jax.tree.map(np.zeros_like, first)
            nu = jax.tree.map(np.zeros_like, first)
        c1, c2 = jnp.float32(1 - b1 ** k), jnp.float32(1 - b2 ** k)
        leaves, tree = jax.tree.flatten(p)
        new = []
        for w, gl, m, v in zip(leaves, jax.tree.leaves(g),
                               jax.tree.leaves(mu), jax.tree.leaves(nu)):
            w, m, v = step(w, gl, m, v, scale, c1, c2)
            new.append((w, *jax.device_get((m, v))))
        del g, leaves
        p = jax.tree.unflatten(tree, [w for w, _, _ in new])
        mu = jax.tree.unflatten(tree, [m for _, m, _ in new])
        nu = jax.tree.unflatten(tree, [v for _, _, v in new])
        losses.append(float(loss))
    return {"losses": losses, "first_grad": first,
            "first_grad_norm": float(first_norm), "params": p}
