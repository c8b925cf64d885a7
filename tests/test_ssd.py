"""The chunk-parallel SSD scan against the plain token-serial recurrence.

``ssd_chunked`` computes the intra-chunk outputs and each chunk's own
state contribution for all chunks at once, and carries only the
(B, H, P, N) state from chunk to chunk.  The reference here is the
recurrence it must equal, one token at a time in float32:

    state = exp(dt·A) · state + dt · x ⊗ B,    y = C · state + D · x
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nn.ssd import ssd_chunked

H, P, N = 3, 4, 5


def serial_ssd(x, dt, a, bmat, cmat, d_skip):
    """Token-serial float32 SSD: the definition, no chunking."""
    x, bmat, cmat = (t.astype(jnp.float32) for t in (x, bmat, cmat))
    b, _, h, p = x.shape

    def step(state, inp):
        xt, dtt, bt, ct = inp                    # (B,H,P) (B,H) (B,N) (B,N)
        state = (jnp.exp(dtt * a)[:, :, None, None] * state
                 + jnp.einsum("bhp,bn->bhpn", dtt[..., None] * xt, bt))
        y = jnp.einsum("bn,bhpn->bhp", ct, state) + d_skip[None, :, None] * xt
        return state, y

    state0 = jnp.zeros((b, h, p, bmat.shape[-1]), jnp.float32)
    seq_first = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
    _, ys = jax.lax.scan(step, state0, tuple(
        seq_first(t) for t in (x, dt, bmat, cmat)))
    return jnp.moveaxis(ys, 0, 1)


def _inputs(s, seed=0, batch=2):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (batch, s, H, P), jnp.float32)
    dt = jax.random.uniform(k[1], (batch, s, H), jnp.float32, 0.01, 0.3)
    a = -jax.random.uniform(k[2], (H,), jnp.float32, 0.5, 4.0)
    bmat = jax.random.normal(k[3], (batch, s, N), jnp.float32)
    cmat = jax.random.normal(k[4], (batch, s, N), jnp.float32)
    d_skip = jax.random.normal(k[5], (H,), jnp.float32)
    return x, dt, a, bmat, cmat, d_skip


def _gap(got, want):
    """Worst gap over the reference's largest magnitude."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def _loss_grads(fn, args, cot):
    """Gradients w.r.t. x, dt, A, B and C of <fn(...), cot>."""
    def loss(x, dt, a, bmat, cmat):
        y = fn(x, dt, a, bmat, cmat, args[5]).astype(jnp.float32)
        return jnp.sum(y * cot)
    return jax.grad(loss, argnums=range(5))(*args[:5])


CASES = [(12, 8), (64, 16), (40, 64)]      # padded tail, nc = 4, S < Q
CASE_IDS = ["padded_tail", "four_chunks", "shorter_than_chunk"]


@pytest.mark.parametrize("s,q", CASES, ids=CASE_IDS)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["fp32", "bf16"])
def test_ssd_chunked_matches_serial_recurrence(s, q, dtype, tol):
    """Values and gradients (x, dt, A, B, C) against the float32
    token-serial reference.  In bf16 the operands of the large einsums
    are rounded to 8 bits of mantissa (2**-8 = 3.9e-3 relative) and the
    outputs sum up to Q such terms: the bound is a few times that."""
    args = _inputs(s)
    cast = (args[0].astype(dtype), args[1], args[2], args[3].astype(dtype),
            args[4].astype(dtype), args[5])
    chunked = lambda *t: ssd_chunked(*t, chunk=q)  # noqa: E731
    y = chunked(*cast)
    assert y.shape == args[0].shape and y.dtype == dtype
    y_ref = serial_ssd(*args)
    assert _gap(y, y_ref) < tol

    cot = jax.random.normal(jax.random.key(7), y_ref.shape, jnp.float32)
    grads = _loss_grads(chunked, cast, cot)
    grads_ref = _loss_grads(serial_ssd, args, cot)
    for name, g, g_ref in zip(("x", "dt", "A", "B", "C"), grads, grads_ref):
        assert np.all(np.isfinite(np.asarray(g, np.float32))), name
        assert _gap(g, g_ref) < tol, (name, _gap(g, g_ref))


def _loops(jaxpr):
    """Bodies of every scan and while loop in ``jaxpr``, nested included."""
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            if eqn.primitive.name in ("scan", "while"):
                yield eqn.primitive.name, sub
            yield from _loops(sub)


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_chunk_loop_is_only_the_state_recurrence(pass_):
    """The loop over chunks carries the (B,H,P,N) state and nothing else:
    no matmul and no (Q, Q) intra-chunk tensor in its body, forward or
    transposed.  The quadratic form runs for all chunks at once."""
    s, q = 64, 16
    args = _inputs(s)
    fn = lambda *t: ssd_chunked(*t, chunk=q)  # noqa: E731
    if pass_ == "forward":
        closed = jax.make_jaxpr(fn)(*args)
    else:
        cot = jnp.ones(args[0].shape, jnp.float32)
        closed = jax.make_jaxpr(
            lambda *t: _loss_grads(fn, t, cot))(*args)
    loops = list(_loops(closed.jaxpr))
    assert loops, "the state recurrence should be a loop over chunks"
    for kind, body in loops:
        for eqn in _all_eqns(body):
            assert eqn.primitive.name != "dot_general", (kind, eqn)
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(v.aval, "shape", ())
                assert tuple(shape[-2:]) != (q, q), (kind, eqn)
