"""Plain float32 Mamba-2 language model: loss, gradients, AdamW steps.

Each block is RMSNorm -> projections z, x, B, C, dt -> causal depthwise
conv (width 4) + SiLU on x, B, C -> the SSD mixer -> gated RMSNorm
``norm(y * silu(z))`` -> output projection, added to the residual; a
final RMSNorm and the tied embedding give the logits (Dao & Gu 2024,
arXiv:2405.21060).  The mixer is written as the paper's quadratic
"attention" form over the whole sequence, not the chunked scan the
program runs:

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{s < u <= t} dt_u A) dt_s x_s + D x_t

Sequences are taken one row at a time and each block is recomputed in
the backward pass, so that the (S, S, heads) decay matrix of one row is
the largest thing alive.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import ops


def _conv(x, w):
    """Causal depthwise conv: y_t = sum_i x_{t-W+1+i} w_i; x (S, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    return sum(xp[i:i + x.shape[0]] * w[i] for i in range(width))


def _block(x, p, *, heads: int, eps: float, fp8: bool):
    e = functools.partial(ops.einsum, fp8_operands=fp8)
    s = p["ssd"]
    h = ops.rms_norm(x, p["pre_norm"]["scale"], eps)
    z = e("sd,di->si", h, s["w_z"])
    xs = jax.nn.silu(_conv(e("sd,di->si", h, s["w_x"]), s["conv_x"]))
    b = jax.nn.silu(_conv(e("sd,dn->sn", h, s["w_B"]), s["conv_B"]))
    c = jax.nn.silu(_conv(e("sd,dn->sn", h, s["w_C"]), s["conv_C"]))
    dt = jax.nn.softplus(e("sd,dh->sh", h, s["w_dt"]) + s["dt_bias"])
    a = -jnp.exp(s["A_log"])
    cum = jnp.cumsum(dt * a, axis=0)                          # (S, H)
    n = x.shape[0]
    causal = jnp.tril(jnp.ones((n, n), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, cum[:, None, :] - cum[None, :, :],
                              -jnp.inf))                     # (t, s, H)
    m = e("tn,sn->ts", c, b)[:, :, None] * decay * dt[None, :, :]
    xh = xs.reshape(n, heads, -1)
    y = e("tsh,shp->thp", m, xh) + s["D"][None, :, None] * xh
    y = y.reshape(n, -1) * jax.nn.silu(z)
    y = ops.rms_norm(y, s["norm_w"], eps)
    return x + e("si,id->sd", y, s["w_out"])


def row_loss(params, inputs, targets, *, heads: int, eps: float,
             fp8: bool = False):
    """Mean next-token cross-entropy of one row (S,) of token ids."""
    tok = params["embed"]["tok"]
    x = tok[inputs]
    block = jax.checkpoint(functools.partial(_block, heads=heads, eps=eps,
                                             fp8=fp8))
    x, _ = jax.lax.scan(lambda x, p: (block(x, p["b0"]), None), x,
                        params["scan"])
    x = ops.rms_norm(x, params["final_norm"]["scale"], eps)
    logits = ops.einsum("sd,vd->sv", x, tok, fp8_operands=fp8)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, targets[:, None],
                                              axis=-1)[:, 0])


@functools.partial(jax.jit, static_argnames=("heads", "eps", "fp8"))
def _loss_and_grad(params, inputs, targets, *, heads, eps, fp8):
    grad_fn = jax.value_and_grad(functools.partial(row_loss, heads=heads,
                                                   eps=eps, fp8=fp8))

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
    m, pub = config["model"], config["published"]
    if set(params["scan"]) != {"b0"} or "tail0" in params:
        raise ValueError("expected a scanned single-kind layer stack")
    return _loss_and_grad(
        ops.f32(params), jnp.asarray(inputs), jnp.asarray(targets),
        heads=m["d_model"] * m["ssm_expand"] // m["ssm_headdim"],
        eps=float(pub["norm_epsilon"]), fp8=fp8)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


def adamw_steps(params, batches, config: dict, hp: dict, fp8: bool = False):
    """AdamW with global-norm clipping over ``batches``, as ``hp`` (the
    job's optimizer settings) states; weight decay on every leaf.
    Returns the losses, the first step's gradient as the optimizer got
    it (clipped), its pre-clip global norm, and the final parameters."""
    p = ops.f32(params)
    mu = jax.tree.map(jnp.zeros_like, p)
    nu = jax.tree.map(jnp.zeros_like, p)
    b1, b2, lr = hp["beta1"], hp["beta2"], hp["learning_rate"]
    eps, wd, clip = hp.get("eps", 1e-8), hp["weight_decay"], hp["grad_clip"]
    losses, first, first_norm = [], None, None
    for k, (inputs, targets) in enumerate(batches, start=1):
        loss, g = loss_and_grad(p, inputs, targets, config, fp8)
        norm = global_norm(g)
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / norm), g)
        if first is None:
            first, first_norm = g, norm
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** k, 1 - b2 ** k
        p = jax.tree.map(
            lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * w), p, mu, nu)
        losses.append(float(loss))
    return {"losses": losses, "first_grad": first,
            "first_grad_norm": float(first_norm), "params": p}
