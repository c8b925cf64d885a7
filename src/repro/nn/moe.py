"""Top-k Mixture of Experts with static-capacity dispatch (GShard-style).

Routing runs **in fp32** (`mpx.force_full_precision`) — router logits and
top-k softmax are the most precision-sensitive computation in an MoE and a
canonical application of the paper's technique (DESIGN.md §4).

Dispatch avoids the O(T·E·C) one-hot dispatch tensor of the classic einsum
formulation: assignment ranks come from a cumsum over a (T·k, E) one-hot,
and tokens move through a scatter-add into an (E, C, d) buffer and a gather
back.  Memory is O(T·k·d + E·C·d), which is what makes the 32k-prefill MoE
cells lowerable.  Tokens beyond an expert's capacity are dropped (standard
top-k-with-capacity semantics); the residual connection carries them.

Sharding: the expert dim maps to the "model" mesh axis when divisible
(phi3.5: 16 experts on 16-way TP = pure expert parallelism); otherwise the
expert-internal hidden dim is TP-sharded (mixtral: 8 experts, d_ff 14336).
Both come from the same rule table — no code change.

:func:`held_experts_apply` is the expert-only block of the DeepSeek-V3
family (Nemotron-H): a sigmoid router over all ``n_routed`` experts, of
which this layer holds a contiguous range, squared-ReLU experts run as a
grouped matmul (megablox ``gmm``) over the held experts' tokens only,
with no capacity and no drop, and an always-on shared expert.  The held
assignments go to a buffer sized on the device from a short ladder of
static sizes, the smallest that takes them all, and the combine
scatter-adds the buffer's rows to their tokens, so that the routing's
data movement follows the rows routed; the rows picked are counted
(``moe_dispatch_rows``).  Routing, sort, dispatch and combine run under
the ``moe_route`` scope inside ``moe``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import mpx
from repro.nn.param import ParamSpec
from repro.obs.trace import scope
from repro.sharding import rules as R
from repro.sharding.rules import shard


def moe_spec(d_model: int, d_ff: int, n_experts: int, kind: str = "swiglu"):
    spec = {
        "router": ParamSpec((d_model, n_experts), ("embed", "experts")),
        "w_up": ParamSpec((n_experts, d_model, d_ff),
                          ("experts", "embed", "moe_mlp"), fan_in=d_model),
        "w_down": ParamSpec((n_experts, d_ff, d_model),
                            ("experts", "moe_mlp", "embed"), fan_in=d_ff),
    }
    if kind in ("swiglu", "geglu"):
        spec["w_gate"] = ParamSpec((n_experts, d_model, d_ff),
                                   ("experts", "embed", "moe_mlp"),
                                   fan_in=d_model)
    return spec


def _route_and_rank(params, xf, *, n_experts: int, top_k: int,
                    capacity: int):
    """Per-group routing + assignment ranks.  xf (T_g, d)."""
    t = xf.shape[0]

    def _route(xin):
        return xin @ params["router"].astype(jnp.float32)

    logits = mpx.force_full_precision(_route, None)(xf)          # (T,E) fp32
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, top_k)               # (T,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)  # renorm

    me = probs.mean(axis=0)                                      # (E,)
    ce_frac = jnp.zeros((n_experts,), jnp.float32).at[expert_idx.reshape(-1)] \
        .add(1.0) / (t * top_k)
    lb_loss = n_experts * jnp.sum(me * ce_frac)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = lb_loss + 1e-3 * z_loss

    flat_e = expert_idx.reshape(-1)                              # (T·k,)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
    keep = pos < capacity
    pos_c = jnp.minimum(pos, capacity - 1)
    return flat_e, pos_c, keep, gate, aux


def _dispatch(xf, flat_e, pos_c, keep, *, n_experts: int, top_k: int,
              capacity: int):
    """Scatter one group's tokens into (E, C, d)."""
    t, d = xf.shape
    token_idx = jnp.repeat(jnp.arange(t), top_k)
    contrib = jnp.where(keep[:, None], xf[token_idx], 0).astype(xf.dtype)
    x_e = jnp.zeros((n_experts, capacity, d), xf.dtype)
    return x_e.at[flat_e, pos_c].add(contrib)


def _combine(y_e, flat_e, pos_c, keep, gate, *, top_k: int):
    """Gather one group's expert outputs back to (T_g, d)."""
    t = gate.shape[0]
    d = y_e.shape[-1]
    y_assign = y_e[flat_e, pos_c]                                # (T·k, d)
    y_assign = jnp.where(keep[:, None], y_assign, 0)
    weighted = y_assign.astype(jnp.float32) * gate.reshape(-1)[:, None]
    return weighted.reshape(t, top_k, d).sum(axis=1).astype(y_e.dtype)


def _expert_ffn(params, x_e, kind: str):
    """(..., E, C, d) -> (..., E, C, d); EP or TP per the rule table."""
    dtype = x_e.dtype
    if kind in ("swiglu", "geglu"):
        g = jnp.einsum("...ecd,edf->...ecf", x_e,
                       params["w_gate"].astype(dtype))
        u = jnp.einsum("...ecd,edf->...ecf", x_e,
                       params["w_up"].astype(dtype))
        act = jax.nn.silu(g) if kind == "swiglu" else jax.nn.gelu(g)
        h = act * u
    else:
        h = jax.nn.gelu(jnp.einsum("...ecd,edf->...ecf", x_e,
                                   params["w_up"].astype(dtype)))
    h = shard(h, ("moe_group", "experts", "exp_cap", "moe_mlp"))
    return jnp.einsum("...ecf,efd->...ecd", h, params["w_down"].astype(dtype))


def _moe_one_group(params, xf: jnp.ndarray, *, n_experts: int, top_k: int,
                   kind: str, capacity: int):
    """Unsharded single-group path (unit tests / no-mesh execution)."""
    flat_e, pos_c, keep, gate, aux = _route_and_rank(
        params, xf, n_experts=n_experts, top_k=top_k, capacity=capacity)
    x_e = _dispatch(xf, flat_e, pos_c, keep, n_experts=n_experts,
                    top_k=top_k, capacity=capacity)
    y_e = _expert_ffn(params, x_e, kind)
    out = _combine(y_e, flat_e, pos_c, keep, gate, top_k=top_k)
    return out, aux.astype(jnp.float32)


def moe_decode_apply(params, x: jnp.ndarray, *, n_experts: int, top_k: int,
                     kind: str = "swiglu") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Small-batch decode fast path: dense per-token expert gather.

    The capacity-buffer scatter is built for prefill-sized T: it zeros and
    scatters an (E, C, d) buffer whose cost is independent of how few
    tokens actually flow, so at decode sizes (T = B·window, tens of
    tokens) dispatch dominates the expert FLOPs.  It is also
    batch-coupled — capacity drops depend on which *other* requests share
    the step — which is wrong for serving determinism.  Here each token
    just gathers its top-k experts' weight matrices and runs them
    directly: exact (no drops, per-token independent), O(T·k·d·f) gathered
    weights, affordable precisely because T is decode-sized.  Routing and
    the combine weighting stay fp32 (same policy as ``moe_apply``); the
    aux loss is meaningless at inference and returns 0.
    """
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    dtype = x.dtype

    def _route(xin):
        return xin @ params["router"].astype(jnp.float32)

    logits = mpx.force_full_precision(_route, None)(xf)          # (T,E) fp32
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, top_k)               # (T,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    w_up = params["w_up"][expert_idx].astype(dtype)              # (T,k,d,f)
    w_down = params["w_down"][expert_idx].astype(dtype)          # (T,k,f,d)
    u = jnp.einsum("td,tkdf->tkf", xf, w_up)
    if kind in ("swiglu", "geglu"):
        gmat = jnp.einsum("td,tkdf->tkf", xf,
                          params["w_gate"][expert_idx].astype(dtype))
        act = jax.nn.silu(gmat) if kind == "swiglu" else jax.nn.gelu(gmat)
        h = act * u
    else:
        h = jax.nn.gelu(u)
    y = jnp.einsum("tkf,tkfd->tkd", h, w_down)
    out = (y.astype(jnp.float32) * gate[..., None]).sum(axis=1)
    return out.reshape(b, s, d).astype(dtype), jnp.zeros((), jnp.float32)


def moe_apply(params, x: jnp.ndarray, *, n_experts: int, top_k: int,
              kind: str = "swiglu", capacity_factor: float = 1.25,
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,d) -> (out (B,S,d), aux_loss scalar fp32).

    Distribution (§Perf iteration A, see EXPERIMENTS.md): GSPMD partitions
    the dispatch *scatter* poorly — it replicates the (E, C, d) buffers,
    inserting ~38 GiB of per-layer all-gather/all-reduce on the production
    mesh.  So when a mesh is installed, the whole dispatch/compute/combine
    runs inside ``shard_map`` MANUAL over the data axes (each DP shard
    dispatches its own tokens into a local capacity buffer — GShard group
    semantics, zero cross-data collectives) while the model axis stays AUTO
    so the expert einsums keep their EP/TP GSPMD sharding.  Without a mesh
    (unit tests) the same body runs directly with one global group.
    """
    b, s, d = x.shape
    mesh, _ = R._get_ctx()
    dp_axes = tuple(ax for ax in ("pod", "data")
                    if mesh is not None and ax in mesh.shape
                    and mesh.shape[ax] > 1)
    groups = 1
    for ax in dp_axes:
        groups *= mesh.shape[ax]
    if b % groups:          # microbatch smaller than the DP section
        groups = 1
    t_g = (b // groups) * s
    capacity = int(math.ceil(t_g * top_k / n_experts * capacity_factor))

    if groups <= 1:
        out, aux = _moe_one_group(params, x.reshape(b * s, d),
                                  n_experts=n_experts, top_k=top_k,
                                  kind=kind, capacity=capacity)
        return out.reshape(b, s, d), aux

    # Staged, vmapped-over-groups pipeline with explicit sharding
    # constraints between stages.  The vmapped scatter/gather become
    # operand-batched ops whose batch (group) dim GSPMD keeps sharded on
    # the data axes — verified to eliminate the replicated-dispatch
    # collectives (EXPERIMENTS.md §Perf iteration A).
    xg = shard(x.reshape(groups, t_g, d), ("moe_group", None, "embed"))
    flat_e, pos_c, keep, gate, aux = jax.vmap(
        functools.partial(_route_and_rank, params, n_experts=n_experts,
                          top_k=top_k, capacity=capacity))(xg)
    x_e = jax.vmap(functools.partial(_dispatch, n_experts=n_experts,
                                     top_k=top_k, capacity=capacity)
                   )(xg, flat_e, pos_c, keep)
    x_e = shard(x_e, ("moe_group", "experts", "exp_cap", "embed"))
    y_e = _expert_ffn(params, x_e, kind)
    y_e = shard(y_e, ("moe_group", "experts", "exp_cap", "embed"))
    out = jax.vmap(functools.partial(_combine, top_k=top_k)
                   )(y_e, flat_e, pos_c, keep, gate)
    out = shard(out, ("moe_group", None, "embed"))
    return out.reshape(b, s, d), jnp.mean(aux)


# --------------------------------------------------------------------------
# expert-only block: a held share of sigmoid-routed experts + shared expert
# --------------------------------------------------------------------------

#: rows of a grouped-matmul tile: each held expert's last tile is padding
GMM_TILE_M = 128
#: the held experts' buffer sizes: the worst case over powers of this
DISPATCH_RATIO = 4
DISPATCH_STEPS = 4


def held_experts_spec(d_model: int, d_ff: int, n_routed: int, held: int,
                      shared_d_ff: int):
    """Router over all ``n_routed`` experts; the ``held`` experts' up and
    down projections with the expert axis second, so that each leaf's
    first axis is its input features; the shared expert."""
    spec = {
        "router": ParamSpec((d_model, n_routed), ("embed", "experts")),
        "w_up": ParamSpec((d_model, held, d_ff),
                          ("embed", "experts", "moe_mlp"), fan_in=d_model),
        "w_down": ParamSpec((d_ff, held, d_model),
                            ("moe_mlp", "experts", "embed"), fan_in=d_ff),
    }
    if shared_d_ff:
        spec["shared_up"] = ParamSpec((d_model, shared_d_ff), ("embed", "mlp"))
        spec["shared_down"] = ParamSpec((shared_d_ff, d_model),
                                        ("mlp", "embed"))
    return spec


def _interpret() -> bool:
    """Pallas kernels run natively on a TPU, interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _tile(dim: int) -> int:
    """The widest of 512, 384, 256, 128 that divides ``dim``, else ``dim``."""
    return next((t for t in (512, 384, 256, 128) if dim % t == 0), dim)


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    return GMM_TILE_M, _tile(k), _tile(n)


def _gmm(lhs, rhs, sizes, transpose_rhs: bool = False):
    """``lhs`` rows in groups of ``sizes`` (one more group than ``rhs``
    has: the rows no held expert takes, whose output is zero) times their
    group's (k, n) matrix of ``rhs`` (or its transpose)."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend
    return backend.gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiling, None, None,
                       transpose_rhs=transpose_rhs, interpret=_interpret())


def _tgmm(lhs, rhs, sizes, groups: int):
    """Each of the first ``groups`` groups' ``lhs`` rows, transposed,
    times its ``rhs`` rows: (groups, k, n)."""
    from jax.experimental.pallas.ops.tpu.megablox.ops import backend
    return backend.tgmm(lhs.swapaxes(0, 1), rhs, sizes, lhs.dtype,
                        _gmm_tiling, None, groups, interpret=_interpret())


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _dispatch_sizes(m: int) -> tuple[int, ...]:
    """The buffer sizes a held-expert layer picks from: ``m``, ``m/4``,
    ``m/16``, ``m/64``, each a whole number of grouped-matmul tiles, in
    increasing order."""
    sizes = {max(-(-m // DISPATCH_RATIO ** i // GMM_TILE_M), 1) * GMM_TILE_M
             for i in range(DISPATCH_STEPS)}
    return tuple(sorted(sizes))


def _buffer(r: int, top_k: int, order, counts, gate):
    """A buffer of ``r`` rows: the assignments it takes (the held ones
    first, by expert), their tokens, the grouped matmul's group sizes
    and each row's gate (zero past the held rows)."""
    with scope("moe_route"):
        rows = order[:r]
        n = counts.sum()
        sizes = jnp.concatenate([counts, (r - n)[None]])
        wt = jnp.where(jnp.arange(r) < n, gate[rows], 0.0)
    return rows, rows // top_k, sizes, wt


def _expert_rows(r: int, top_k: int, xf, order, counts, gate, w_up, w_down):
    """The held assignments through their experts in a buffer of ``r``
    rows, each row's output added to its token times its gate: (T, d)."""
    rows, token, sizes, wt = _buffer(r, top_k, order, counts, gate)
    with scope("moe_route"):
        xs = xf[token]                                          # (r,d)
    with scope("moe"):
        ys = _gmm(_relu2(_gmm(xs, w_up, sizes)), w_down, sizes)
    with scope("moe_route"):
        out = jnp.zeros(xf.shape, jnp.float32).at[token].add(
            ys.astype(jnp.float32) * wt[:, None])
    return out.astype(xf.dtype)


def _expert_rows_bwd(r: int, top_k: int, xf, order, counts, gate, w_up,
                     w_down, g):
    """:func:`_expert_rows`' gradient for ``xf``, ``gate``, ``w_up`` and
    ``w_down``.  The buffer's forward is made again from the inputs.  The
    gate scales the down projection's input and its gradient, not the
    buffer's cotangent, so that this is a plain gather of ``g`` and the
    gate's gradient needs no ``ys``: autodiff would hold the gathered
    cotangent, its gated copy and ``ys`` at once, each as large as the
    buffer."""
    f32 = jnp.float32
    rows, token, sizes, wt = _buffer(r, top_k, order, counts, gate)
    with scope("moe_route"):
        xs = xf[token]
        gr = g[token]                                           # (r,d)
    with scope("moe"):
        pre = _gmm(xs, w_up, sizes)
        d_h = _gmm(gr, w_down, sizes, transpose_rhs=True).astype(f32)
        relu = jax.nn.relu(pre.astype(f32))
        d_wt = jnp.sum(d_h * relu * relu, -1)
        d_w_down = _tgmm((relu * relu * wt[:, None]).astype(xs.dtype), gr,
                         sizes, w_down.shape[0])
        d_pre = (d_h * wt[:, None] * 2 * relu).astype(xs.dtype)
        d_xs = _gmm(d_pre, w_up, sizes, transpose_rhs=True)
        d_w_up = _tgmm(xs, d_pre, sizes, w_up.shape[0])
    with scope("moe_route"):
        d_xf = jnp.zeros_like(xf).at[token].add(d_xs)
        d_gate = jnp.zeros_like(gate).at[rows].add(
            jnp.where(jnp.arange(r) < counts.sum(), d_wt, 0.0))
    return d_xf, d_gate, d_w_up, d_w_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_experts(ladder, top_k, pick, xf, order, counts, gate, w_up,
                  w_down):
    """:func:`_expert_rows` in the buffer of ``ladder[pick]`` rows.  Its
    backward saves only the inputs: under autodiff ``lax.switch`` would
    save every branch's residuals, and the branch taken would write
    zeros for the others' buffers."""
    return jax.lax.switch(
        pick, [functools.partial(_expert_rows, r, top_k) for r in ladder],
        xf, order, counts, gate, w_up, w_down)


def _held_experts_fwd(ladder, top_k, pick, *args):
    return _held_experts(ladder, top_k, pick, *args), (pick, args)


def _held_experts_bwd(ladder, top_k, res, g):
    pick, args = res
    d_xf, d_gate, d_w_up, d_w_down = jax.lax.switch(
        pick, [functools.partial(_expert_rows_bwd, r, top_k)
               for r in ladder], *args, g)
    return None, d_xf, None, None, d_gate, d_w_up, d_w_down


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def held_experts_apply(params, x: jnp.ndarray, *, n_routed: int, top_k: int,
                       held: tuple[int, int], routed_scale: float,
                       ) -> tuple[jnp.ndarray, dict]:
    """x (B,S,d) -> (this layer's share of the expert output (B,S,d),
    routing counters).

    The router scores every token against all ``n_routed`` experts in
    fp32 (a float32 matmul at full precision): sigmoid scores, the top
    ``top_k`` of score plus a per-expert correction bias (zero here, and
    not a parameter), weights the picked scores over their sum times
    ``routed_scale``.  Of these assignments the ones to the
    ``held = (first, count)`` experts are sorted by expert to the front.
    They go to a buffer whose size ``lax.switch`` picks on the device:
    the smallest of :func:`_dispatch_sizes` (the most the held experts
    can ever receive, and its quarter, sixteenth and sixty-fourth) that
    takes every held assignment.  None is dropped, and the dispatch
    gather, the grouped matmul's masks and the combine cost the rows
    routed, not the worst case.  The grouped matmul computes only the
    held experts' rows; the combine scatter-adds each buffer row,
    weighted by its gate, to its token.  The shared expert sees every
    token.  Counters: the assignments that reached a held expert, the
    busiest held expert's over the mean, and the buffer rows picked.
    """
    b, s, d = x.shape
    t = b * s
    first, count = held
    dtype = x.dtype
    f32 = jnp.float32
    xf = x.reshape(t, d)
    ladder = _dispatch_sizes(t * min(top_k, count))

    with scope("moe_route"):
        def _route(xin):
            # HIGHEST: a float32 matmul on a TPU otherwise takes one
            # bfloat16 pass, and the top-k would follow its rounding
            return jnp.matmul(xin, params["router"].astype(f32),
                              precision=jax.lax.Precision.HIGHEST)

        logits = mpx.force_full_precision(_route, None)(xf)      # (T,E) fp32
        scores = jax.nn.sigmoid(logits)
        bias = jnp.zeros((n_routed,), f32)      # correction bias: zero
        _, expert = jax.lax.top_k(scores + bias, top_k)         # (T,k)
        gate = jnp.take_along_axis(scores, expert, axis=-1)
        gate = gate / gate.sum(-1, keepdims=True) * routed_scale
        local = expert.reshape(-1) - first                      # (T·k,)
        mine = (local >= 0) & (local < count)
        local = jnp.where(mine, local, count)   # not held: sorted last
        order = jnp.argsort(local, stable=True)
        if ladder[-1] > t * top_k:
            order = jnp.pad(order, (0, ladder[-1] - t * top_k))
        counts = jnp.sum(jax.nn.one_hot(local, count, dtype=jnp.int32), 0)
        n_held = counts.sum()
        gate = gate.reshape(-1)                                 # (T·k,)
        pick = jnp.sum(jnp.asarray(ladder[:-1]) < n_held)

    w_up = params["w_up"].astype(dtype).transpose(1, 0, 2)      # (E,d,f)
    w_down = params["w_down"].astype(dtype).transpose(1, 0, 2)  # (E,f,d)
    out = _held_experts(ladder, top_k, pick, xf, order, counts, gate, w_up,
                        w_down)
    if "shared_up" in params:
        out = out + _relu2(xf @ params["shared_up"].astype(dtype)) \
            @ params["shared_down"].astype(dtype)
    total = n_held.astype(f32)
    stats = {"moe_held_assignments": total,
             "moe_held_load_max": counts.max().astype(f32) * count
             / jnp.maximum(total, 1.0),
             "moe_dispatch_rows": jnp.asarray(ladder, f32)[pick]}
    return out.reshape(b, s, d), stats
