"""Mamba-2 SSD (state-space duality) block.

Implements the chunked SSD algorithm from Dao & Gu (arXiv:2405.21060 §7)
in its chunk-parallel form.  The sequence is cut into chunks of Q tokens.
For all chunks at once, the intra-chunk outputs are an attention-like
quadratic form (CB^T times the causal decay, against x) and each chunk's
own contribution to the state is one matmul; both map onto the MXU.  Only
the compact (B, H, P, N) state recurrence across chunks is sequential: a
``lax.scan`` of ``state * decay + local`` with no matmul in its body, so
cost stays linear in the number of chunks.  The incoming state's
contribution to each chunk's outputs is again computed for all chunks at
once.  O(S·Q) work, O(S) memory, constant-size decode state.

Precision (DESIGN.md §4): `softplus(dt)`, the `dt*A` cumulative sums, all
`exp` decays and the carried and per-chunk states are fp32 — these are
long products of near-one factors, exactly the compounding-rounding shape
MPX's `force_full_precision` exists for.  The large einsums (CB^T,
score·x, state outer products) take compute-dtype operands.

Projections are kept separate per component (z, x, B, C, dt) instead of one
fused in_proj: identical FLOPs, but each output gets its own logical
sharding axis, which is what lets `ssm_inner` TP-shard while B/C/dt stay
replicated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.nn.param import ParamSpec
from repro.sharding.rules import shard


def ssd_spec(d_model: int, d_inner: int, n_heads: int, headdim: int,
             d_state: int, conv_width: int = 4, n_groups: int = 1):
    """B and C hold ``n_groups`` groups of ``d_state`` each, side by side."""
    assert d_inner == n_heads * headdim and n_heads % n_groups == 0
    bc = n_groups * d_state
    return {
        "w_z": ParamSpec((d_model, d_inner), ("embed", "ssm_inner")),
        "w_x": ParamSpec((d_model, d_inner), ("embed", "ssm_inner")),
        "w_B": ParamSpec((d_model, bc), ("embed", "ssm_state")),
        "w_C": ParamSpec((d_model, bc), ("embed", "ssm_state")),
        "w_dt": ParamSpec((d_model, n_heads), ("embed", "ssm_heads")),
        "conv_x": ParamSpec((conv_width, d_inner), (None, "ssm_inner"),
                            init="normal", scale=0.5),
        "conv_B": ParamSpec((conv_width, bc), (None, "ssm_state"),
                            init="normal", scale=0.5),
        "conv_C": ParamSpec((conv_width, bc), (None, "ssm_state"),
                            init="normal", scale=0.5),
        "A_log": ParamSpec((n_heads,), ("ssm_heads",), init="ones",
                           scale=1.386),     # A = -exp(A_log) ≈ -4
        "D": ParamSpec((n_heads,), ("ssm_heads",), init="ones"),
        "dt_bias": ParamSpec((n_heads,), ("ssm_heads",), init="ones",
                             scale=-4.6),    # softplus ≈ 0.01
        "norm_w": ParamSpec((d_inner,), ("ssm_inner",), init="ones"),
        "w_out": ParamSpec((d_inner, d_model), ("ssm_inner", "embed")),
    }


def _conv1d(x, w, state=None):
    """Depthwise causal conv along seq; x (B,S,C), w (W,C)."""
    width = w.shape[0]
    if state is not None:
        hist = jnp.concatenate([state, x], axis=1)
        new_state = hist[:, -(width - 1):]
    else:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
        hist = jnp.concatenate([pad, x], axis=1)
        new_state = None
    y = sum(hist[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
            for i in range(width))
    return jax.nn.silu(y), new_state


def _gated_rmsnorm(w, y, z, groups: int = 1, eps: float = 1e-6):
    """Mamba-2's RMSNorm(y * silu(z)) with fp32 statistics, over each of
    ``groups`` equal slices of the last axis."""
    y32 = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if groups > 1:
        y32 = y32.reshape(*y32.shape[:-1], groups, -1)
    rms = jnp.sqrt(jnp.mean(y32 * y32, axis=-1, keepdims=True) + eps)
    y32 = (y32 / rms).reshape(y.shape)
    return (y32 * w.astype(jnp.float32)).astype(y.dtype)


def ssd_chunked(x, dt, a, bmat, cmat, d_skip, chunk: int):
    """Chunk-parallel SSD scan.

    x (B,S,H,P) compute dtype; dt (B,S,H) fp32 (post-softplus);
    a (H,) fp32 negative; bmat/cmat (B,S,N) compute dtype, one group for
    every head, or (B,S,G,N), G groups with head h reading group
    h // (H/G); d_skip (H,) fp32.  Returns y (B,S,H,P) in x.dtype.

    Chunk-major layout throughout: x enters, and y leaves, as one
    (nc,B,H*P,Q) transpose each, so every per-head matmul has (P, Q) as
    its two minor dims and the chunk axis leads, as the scan wants it.
    With groups the head axis is split (G, H/G) and C.B^T is formed once
    per group; with one group the program is the ungrouped one.
    """
    b, s, h, p = x.shape
    grouped = bmat.ndim == 4
    g = bmat.shape[2] if grouped else 1
    n = bmat.shape[-1]
    heads = (g, h // g) if grouped else (h,)
    hs, gs = ("gh", "g") if grouped else ("h", "")    # einsum head letters
    q = min(chunk, s)
    pad = (-s) % q
    if pad:
        # zero-pad the tail: dt=0 makes padded steps exact no-ops for the
        # state (decay exp(0·a)=1, input dt·x=0); padded y rows are sliced.
        bc_pad = ((0, 0), (0, pad)) + ((0, 0),) * (bmat.ndim - 2)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bmat = jnp.pad(bmat, bc_pad)
        cmat = jnp.pad(cmat, bc_pad)
    s_orig, s = s, s + pad
    nc = s // q
    dtype = x.dtype
    f32 = jnp.float32

    def chunks(m):
        """(B,S[,G],N) -> (nc,B[,G],Q,N)."""
        m = m.reshape(b, nc, q, *m.shape[2:])
        return m.transpose(1, 0, 3, 2, 4) if grouped else \
            m.transpose(1, 0, 2, 3)

    # chunked views, chunk axis first; x head-major with Q minor
    xc = x.reshape(b, nc, q, h * p).transpose(1, 0, 3, 2) \
        .reshape(nc, b, *heads, p, q)
    bc = chunks(bmat)                                         # (nc,B,Q,N)
    cc = chunks(cmat)
    dtc = dt.reshape(b, nc, q, h).transpose(1, 0, 3, 2)       # (nc,B,H,Q)
    if grouped:
        dtc = dtc.reshape(nc, b, *heads, q)
    # within-chunk cumsum as a triangular matmul: the MXU at full fp32
    # precision, where a cumsum lowers to a slow reduce-window
    cs = jnp.einsum(f"cb{hs}s,sl->cb{hs}l", dtc * a.reshape(heads)[..., None],
                    jnp.triu(jnp.ones((q, q), f32)),
                    precision=jax.lax.Precision.HIGHEST)      # fp32, <= 0

    # --- intra-chunk outputs, all chunks at once (attention-like, causal) ---
    cb = jnp.einsum(f"cb{gs}ln,cb{gs}sn->cb{gs}ls", cc, bc,
                    preferred_element_type=f32)
    cb = cb[:, :, :, None] if grouped else cb[:, :, None]
    # mask INSIDE the exponent: the upper triangle would be exp(+large)
    # = inf, and the later 0-masking would turn its cotangent into
    # 0 * inf = NaN.  exp(-1e30) = 0 kills value and gradient cleanly.
    tri = jnp.tril(jnp.ones((q, q), jnp.bool_))
    ldiff = jnp.where(tri, cs[..., :, None] - cs[..., None, :], -1e30)
    scores = (cb * jnp.exp(ldiff)).astype(dtype)              # (nc,B,H,l,s)
    xdt = xc * dtc[..., None, :].astype(dtype)
    y_diag = jnp.einsum(f"cb{hs}ps,cb{hs}ls->cb{hs}pl", xdt, scores)

    # --- each chunk's own state contribution, all chunks at once ---
    total = cs[..., -1]                                       # (nc,B,H)
    w = (jnp.exp(total[..., None] - cs) * dtc).astype(dtype)  # (nc,B,H,Q)
    local = jnp.einsum(f"cb{hs}pq,cb{gs}qn->cb{hs}pn", xc * w[..., None, :],
                       bc, preferred_element_type=f32)        # (nc,B,H,P,N)

    # --- inter-chunk recurrence: the only sequential part ---
    def chunk_step(state, inp):
        """state (B,H,P,N) fp32; emits the state entering the chunk."""
        decay, loc = inp
        return state * decay[..., None, None] + loc, state

    state0 = jnp.zeros((b, *heads, p, n), f32)
    _, states = jax.lax.scan(chunk_step, state0, (jnp.exp(total), local))

    # --- contribution of each chunk's incoming state, all chunks at once ---
    y_off = jnp.einsum(f"cb{hs}pn,cb{gs}ln->cb{hs}pl", states.astype(dtype),
                       cc) * jnp.exp(cs)[..., None, :].astype(dtype)

    y = (y_diag + y_off).astype(dtype).reshape(nc, b, h * p, q)
    y = y.transpose(1, 0, 3, 2).reshape(b, s, h, p)
    y = y + d_skip.astype(dtype)[None, None, :, None] * x
    return y[:, :s_orig] if pad else y


def ssd_block_apply(params, xin, *, n_heads: int, headdim: int, d_state: int,
                    chunk: int = 256, conv_width: int = 4,
                    state: dict | None = None, n_groups: int = 1,
                    eps: float = 1e-6):
    """Full Mamba-2 block.  xin (B,S,d_model) -> same shape.

    ``state`` None for training; dict(conv_x/conv_B/conv_C, ssm) for decode
    — returns (y, new_state) then.  ``n_groups`` > 1 (grouped B/C, and a
    gated norm over each group's heads) is trained, not decoded; ``eps``
    is the gated norm's.
    """
    dtype = xin.dtype
    b, s, _ = xin.shape
    z = xin @ params["w_z"].astype(dtype)
    x = xin @ params["w_x"].astype(dtype)
    bmat = xin @ params["w_B"].astype(dtype)
    cmat = xin @ params["w_C"].astype(dtype)
    dt_raw = (xin @ params["w_dt"].astype(dtype)).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    x = shard(x, ("batch", "seq", "ssm_inner"))

    if state is None:
        x, _ = _conv1d(x, params["conv_x"])
        bmat, _ = _conv1d(bmat, params["conv_B"])
        cmat, _ = _conv1d(cmat, params["conv_C"])
        if n_groups > 1:
            bmat = bmat.reshape(b, s, n_groups, d_state)
            cmat = cmat.reshape(b, s, n_groups, d_state)
        xh = x.reshape(b, s, n_heads, headdim)
        y = ssd_chunked(xh, dt, a, bmat, cmat, params["D"], chunk)
        y = y.reshape(b, s, n_heads * headdim)
        y = _gated_rmsnorm(params["norm_w"], y, z, n_groups, eps)
        out = y @ params["w_out"].astype(dtype)
        return shard(out, ("batch", "seq", "embed"))
    if n_groups > 1:
        raise ValueError("grouped B/C SSD blocks are trained, not decoded")

    # ---- decode: O(1) state update ----
    x, cs_x = _conv1d(x, params["conv_x"], state["conv_x"])
    bmat, cs_b = _conv1d(bmat, params["conv_B"], state["conv_B"])
    cmat, cs_c = _conv1d(cmat, params["conv_C"], state["conv_C"])
    xh = x.reshape(b, 1, n_heads, headdim)[:, 0]        # (B,H,P)
    da = jnp.exp(dt[:, 0] * a)                          # (B,H) fp32
    # state' = exp(dt*A) state + dt * x ⊗ B
    ssm = state["ssm"] * da[:, :, None, None] \
        + jnp.einsum("bhp,bn->bhpn",
                     (dt[:, 0][..., None].astype(dtype) * xh),
                     bmat[:, 0]).astype(jnp.float32)
    y = jnp.einsum("bn,bhpn->bhp", cmat[:, 0], ssm.astype(dtype))
    y = y + params["D"].astype(dtype)[None, :, None] * xh
    y = y.reshape(b, 1, n_heads * headdim)
    y = _gated_rmsnorm(params["norm_w"], y, z, eps=eps)
    out = y @ params["w_out"].astype(dtype)
    return out, {"conv_x": cs_x, "conv_B": cs_b, "conv_C": cs_c, "ssm": ssm}


def ssd_serve_chunk(params, xin, state, valid, *, n_heads: int, headdim: int,
                    d_state: int, conv_width: int = 4):
    """Chunked-prefill / ragged-decode serve entry point.

    xin (B,C,d_model); state dict(conv_x/conv_B/conv_C, ssm) per slot;
    valid (B,) int32 — how many leading positions of each row are real.
    Returns (y (B,C,d_model), new_state).

    Positions are advanced by a sequential per-position ``lax.scan`` that
    executes exactly the decode-branch ops (projections batched — row-wise
    identical matmuls), NOT the chunked quadratic form: the quadratic path
    has a different bf16 summation order, and serving pins greedy token
    identity against per-token ``decode()``.  Padded positions (>= valid)
    produce garbage outputs (never gathered) and are exact state no-ops.
    """
    dtype = xin.dtype
    b, c, _ = xin.shape
    z = xin @ params["w_z"].astype(dtype)
    x = xin @ params["w_x"].astype(dtype)
    bmat = xin @ params["w_B"].astype(dtype)
    cmat = xin @ params["w_C"].astype(dtype)
    dt_raw = (xin @ params["w_dt"].astype(dtype)).astype(jnp.float32)
    dt = jax.nn.softplus(dt_raw + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    x = shard(x, ("batch", "seq", "ssm_inner"))

    def conv_step(w, buf, xt):
        hist = jnp.concatenate([buf, xt[:, None]], axis=1)
        y = sum(hist[:, i] * w[i].astype(xt.dtype) for i in range(w.shape[0]))
        return jax.nn.silu(y), hist[:, 1:]

    def keep(ok, new, old):
        m = ok.reshape((b,) + (1,) * (new.ndim - 1))
        return jnp.where(m, new, old)

    def step(carry, inp):
        cx, cb, cc, ssm = carry
        xt, bt, ct, dtt, ok = inp
        xs, cx_new = conv_step(params["conv_x"], cx, xt)
        bs, cb_new = conv_step(params["conv_B"], cb, bt)
        cs, cc_new = conv_step(params["conv_C"], cc, ct)
        xh = xs.reshape(b, n_heads, headdim)
        da = jnp.exp(dtt * a)
        ssm_new = ssm * da[:, :, None, None] + jnp.einsum(
            "bhp,bn->bhpn", dtt[..., None].astype(dtype) * xh,
            bs).astype(jnp.float32)
        y = jnp.einsum("bn,bhpn->bhp", cs, ssm_new.astype(dtype))
        y = y + params["D"].astype(dtype)[None, :, None] * xh
        carry = (keep(ok, cx_new, cx), keep(ok, cb_new, cb),
                 keep(ok, cc_new, cc), keep(ok, ssm_new, ssm))
        return carry, y

    ok = jnp.arange(c)[:, None] < valid[None, :]             # (C, B)
    init = (state["conv_x"], state["conv_B"], state["conv_C"], state["ssm"])
    (cx, cb, cc, ssm), ys = jax.lax.scan(
        step, init,
        (x.transpose(1, 0, 2), bmat.transpose(1, 0, 2),
         cmat.transpose(1, 0, 2), dt.transpose(1, 0, 2), ok))
    y = ys.transpose(1, 0, 2, 3).reshape(b, c, n_heads * headdim)
    y = _gated_rmsnorm(params["norm_w"], y, z)
    out = y @ params["w_out"].astype(dtype)
    return (shard(out, ("batch", "seq", "embed")),
            {"conv_x": cx, "conv_B": cb, "conv_C": cc, "ssm": ssm})


def ssd_state_spec(batch: int, d_inner: int, d_state: int, n_heads: int,
                   headdim: int, conv_width: int, dtype):
    return {
        "conv_x": jax.ShapeDtypeStruct((batch, conv_width - 1, d_inner), dtype),
        "conv_B": jax.ShapeDtypeStruct((batch, conv_width - 1, d_state), dtype),
        "conv_C": jax.ShapeDtypeStruct((batch, conv_width - 1, d_state), dtype),
        "ssm": jax.ShapeDtypeStruct((batch, n_heads, headdim, d_state),
                                    jnp.float32),
    }
