"""Gated delta rule with a decay for each key channel, in chunks.

The recurrence of Kimi Delta Attention (arXiv:2510.26692), per head, with
a state ``S`` of ``(d_k, d_v)`` that starts at zero::

    S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

No op of the library computed a recurrence before this one, and autodiff
through one step a token is no option at 8192 tokens. This is the chunked
(WY) form: inside a chunk of ``CHUNK`` tokens the rank-one updates are
gathered into ``U = (I + A)^-1 (b V - b K e^G S_0)`` by one triangular
solve, so that a chunk costs a handful of matmuls, and only the state at
each chunk's start is carried from chunk to chunk. Three parts:

``_prepare``   everything that needs no state, for all chunks at once;
``_propagate`` the states at the chunks' starts, one ``lax.scan`` step a
               chunk (two small matmuls), with a hand-written reverse scan
               for its backward;
``_output``    the outputs, for all chunks at once.

The op has its own backward (``jax.custom_vjp``): it keeps its inputs and
the chunk-start states, and the backward runs ``_prepare`` again, so no
``(CHUNK, CHUNK)`` matrix lives from the forward to the backward.

Decay. ``g <= 0`` is the log of the decay, and ``G`` its running sum from
the chunk's start. ``exp(G_t - G_s)`` for ``s <= t`` is at most 1, but the
factorisation ``exp(G_t) * exp(-G_s)`` that turns it into a matmul is not:
at ``g = -5`` a step ``exp(-G_s)`` passes float32 after 18 tokens. So the
chunk is cut into sub-blocks of ``SUB``: between two sub-blocks the sum is
split at the later one's start, ``exp(G_t - r) * exp(r - G_s)`` with both
exponents <= 0, and inside a sub-block the ``(SUB, SUB, d_k)`` terms are
summed as they are. Nothing that can overflow is formed; what underflows
is a contribution that is zero in float32 anyway.

Precision. State, decay and the solve are float32 whatever the inputs
(``gated_delta_rule`` is a FLOAT op of ``amp/lists.py``); the matmuls take
float32 operands at the backend's default precision, the ``(CHUNK,
CHUNK)`` matrices that feed the solve at ``HIGHEST``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
SUB = 16
#: heads that go through the op together: the backward's working set is
#: ~110 MB a head at 8192 tokens, and the groups run one after another
HEAD_GROUP = 8
_HI = lax.Precision.HIGHEST


def _prepare(q, k, v, g, beta):
    """The state-free terms of every chunk. Inputs ``(..., C, d)`` float32
    (``beta`` ``(..., C)``); returns ``qg, kg, w, ut, aqk, decay``."""
    c, dk = k.shape[-2:]
    n = c // SUB
    lead = k.shape[:-2]
    # G_t, the running sum inclusive of t: a (C, C) matmul at full precision
    # (a cumsum lowers to a reduce-window, 1 ms a call for 8 heads on a v5e)
    gsum = jnp.einsum("ts,...sc->...tc", jnp.tril(jnp.ones((c, c), g.dtype)),
                      g, precision=_HI)
    total = gsum[..., -1:, :]                           # G at the chunk's end
    sub = lambda x: x.reshape(*lead, n, SUB, x.shape[-1])
    gs, ks, qs = sub(gsum), sub(k), sub(q)
    start = (gs - sub(g))[..., :1, :]                   # r: G before a sub-block
    near = jnp.exp(gs - start)                          # <= 1
    # s in an earlier sub-block than t: split the sum at t's sub-block start
    def across(x):
        x_near = x * near
        rows = [jnp.zeros((*lead, SUB, c), k.dtype)]
        for i in range(1, n):
            k_far = k[..., :i * SUB, :] * jnp.exp(
                start[..., i, :, :] - gsum[..., :i * SUB, :])   # <= 1
            a = jnp.einsum("...ic,...sc->...is", x_near[..., i, :, :],
                           k_far, precision=_HI)
            rows.append(jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                                + [(0, c - i * SUB)]))
        return jnp.concatenate(rows, -2)

    # s and t in one sub-block: the terms themselves, exponent <= 0
    inside = jnp.exp(jnp.minimum(gs[..., :, None, :] - gs[..., None, :, :], 0.0))
    eye = jnp.eye(n, dtype=k.dtype)

    def within(x):
        d = jnp.sum(x[..., :, None, :] * ks[..., None, :, :] * inside, -1)
        return (d[..., :, :, None, :] * eye[:, None, :, None]).reshape(
            *lead, c, c)

    ones = jnp.ones((c, c), bool)
    a_kk = jnp.where(jnp.tril(ones, -1), across(ks) + within(ks),
                     0.0) * beta[..., None]
    aqk = jnp.where(jnp.tril(ones), across(qs) + within(qs), 0.0)
    from_start = jnp.exp(gsum)                          # <= 1
    rhs = jnp.concatenate([k * from_start, v], -1) * beta[..., None]
    solved = lax.linalg.triangular_solve(
        a_kk + jnp.eye(c, dtype=k.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, ut = solved[..., :dk], solved[..., dk:]
    return (q * from_start, k * jnp.exp(total - gsum), w, ut, aqk,
            jnp.exp(total[..., 0, :]))


def _chunk_step(state, w, ut, kg, decay):
    u = ut - w @ state
    return decay[..., None] * state + jnp.swapaxes(kg, -1, -2) @ u


def _propagate(w, ut, kg, decay):
    """States at the chunks' starts, ``(N, ..., dk, dv)``; chunks lead."""
    def step(state, xs):
        return _chunk_step(state, *xs), state
    zero = jnp.zeros((*w.shape[1:-2], w.shape[-1], ut.shape[-1]), w.dtype)
    return lax.scan(step, zero, (w, ut, kg, decay))[1]


def _propagate_bwd(w, ut, kg, decay, states, d_states):
    """Cotangents of ``w, ut, kg, decay`` for ``d_states`` on the chunk-start
    states: the reverse scan, with ``u`` computed again from the state."""
    def step(lam, xs):                  # lam: cotangent of the next state
        w, ut, kg, decay, state, d_state = xs
        u = ut - w @ state
        d_u = kg @ lam
        d_kg = u @ jnp.swapaxes(lam, -1, -2)
        d_decay = jnp.sum(state * lam, -1)
        d_w = -d_u @ jnp.swapaxes(state, -1, -2)
        lam = d_state + decay[..., None] * lam - jnp.swapaxes(w, -1, -2) @ d_u
        return lam, (d_w, d_u, d_kg, d_decay)
    return lax.scan(step, jnp.zeros_like(states[0]),
                    (w, ut, kg, decay, states, d_states), reverse=True)[1]


def _output(qg, aqk, w, ut, states):
    return qg @ states + aqk @ (ut - w @ states)


def _chunked(x):
    """``(B, T, H, d)`` -> ``(N, B, H, C, d)`` float32."""
    b, t, h = x.shape[:3]
    x = x.astype(jnp.float32).reshape(b, t // CHUNK, CHUNK, h, -1)
    return jnp.transpose(x, (1, 0, 3, 2, 4))


def _prepared(q, k, v, g, beta):
    """``_prepare`` of the op's own inputs, cut into chunks."""
    return _prepare(*map(_chunked, (q, k, v, g)),
                    _chunked(beta[..., None])[..., 0])


def _forward(q, k, v, g, beta):
    from apex_tpu.amp.functional_patch import suspend
    with suspend():                     # float32 here whatever the policy
        with jax.named_scope("kda/scan"):
            qg, kg, w, ut, aqk, decay = _prepared(q, k, v, g, beta)
            states = _propagate(w, ut, kg, decay)
            out = _output(qg, aqk, w, ut, states)
    n, b, h, c, dv = out.shape
    return jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(b, n * c, h, dv), states


@jax.custom_vjp
def _scan(q, k, v, g, beta):
    return _forward(q, k, v, g, beta)[0]


def _scan_fwd(q, k, v, g, beta):
    out, states = _forward(q, k, v, g, beta)
    return out, (q, k, v, g, beta, states)


def _scan_bwd(res, d_out):
    from apex_tpu.amp.functional_patch import suspend
    q, k, v, g, beta, states = res
    with suspend(), jax.named_scope("kda/scan"):
        (qg, kg, w, ut, aqk, decay), back = jax.vjp(_prepared, q, k, v, g, beta)
        _, out_back = jax.vjp(_output, qg, aqk, w, ut, states)
        d_qg, d_aqk, d_w, d_ut, d_states = out_back(_chunked(d_out))
        p_w, p_ut, d_kg, d_decay = _propagate_bwd(w, ut, kg, decay, states,
                                                  d_states)
        return back((d_qg, d_kg, d_w + p_w, d_ut + p_ut, d_aqk, d_decay))


_scan.defvjp(_scan_fwd, _scan_bwd)


def gated_delta_rule(q, k, v, g, beta):
    """``o_t = S_t^T q_t`` of the recurrence above, for every token.

    ``q, k, g``: ``(B, T, H, d_k)``; ``v``: ``(B, T, H, d_v)``; ``beta``:
    ``(B, T, H)``. ``g`` is the log of the decay (``<= 0``), a value for
    each key channel. The caller normalises and scales ``q`` and ``k``.
    Returns ``(B, T, H, d_v)`` in float32. Any length: a sequence is padded
    to whole chunks with tokens that leave the state as it is.
    """
    t = q.shape[1]
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    h = q.shape[2]
    if h > HEAD_GROUP and h % HEAD_GROUP == 0:
        grouped = lambda x: jnp.moveaxis(x.reshape(
            *x.shape[:2], h // HEAD_GROUP, HEAD_GROUP, *x.shape[3:]), 2, 0)
        out = lax.map(lambda xs: _scan(*xs),
                      tuple(map(grouped, (q, k, v, g, beta))))
        out = jnp.moveaxis(out, 0, 2).reshape(*q.shape[:3], -1)
    else:
        out = _scan(q, k, v, g, beta)
    return out[:, :t]


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence as written, one ``lax.scan`` step a token: the oracle
    of the tests."""
    f32 = lambda x: jnp.swapaxes(x.astype(jnp.float32), 0, 1)

    def step(state, xs):
        q, k, v, g, beta = xs
        state = state * jnp.exp(g)[..., None]
        u = (v - jnp.einsum("bhkv,bhk->bhv", state, k)) * beta[..., None]
        state = state + k[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    b, _, h, dk = q.shape
    zero = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    out = lax.scan(step, zero, tuple(map(f32, (q, k, v, g, beta))))[1]
    return jnp.swapaxes(out, 0, 1)
