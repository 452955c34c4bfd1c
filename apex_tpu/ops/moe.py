"""Routed experts, for a layer that holds some of them.

Under expert parallelism every rank routes each token over *all* experts
and computes the part of the result that its own experts give; the parts
are summed across ranks. These are that rank's two steps: :func:`route`
(scores over every expert, in float32) and :func:`held_experts` (the
weighted sum over the chosen experts that are in ``held``). On one chip
there is no exchange, and nothing here stands in for the absent ranks.

No token is dropped. A held expert whose rows fit a capacity (eight times
an even share, whole sublanes) has them compacted into its block, and the
blocks run as one batched matmul over ``(held, capacity)`` rows. An expert
that got more runs over every row under a 0/1 mask instead
(``moe/overflow``), in a loop that takes one turn for each such expert and
none in a step that has none. Both give the same value up to the order of a
sum: which experts overflow is read on the device each step from the routing
it sees, so the cost depends on the routing (by one expert's dense matmuls
for each that overflows) and the result does not. One ``custom_vjp`` spans
both: the backward keeps the layer's inputs and runs the forward again, so
that no expert's activations wait for the backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: a held expert's capacity, as a multiple of its even share of the rows.
#: Nothing balances the load here (no auxiliary loss, a selection bias that
#: no gradient moves), and a router that trains drifts: on the v5e, at 8192
#: rows and 8 of 256 experts held, some expert passed twice its share in a
#: third of the layer-steps and four times in one in ten (PR 28), each time
#: paying a dense expert's matmuls over every row. At 8 the blocks' padding
#: costs as much as two such experts, every step
CAPACITY_FACTOR = 8.0


def route(x, router, bias, top_k, scale, scoring="sigmoid"):
    """Scores over every expert (``scoring``: ``"sigmoid"``, each expert's
    own, or ``"softmax"``, over all of them), the ``top_k`` of ``score +
    bias`` chosen (``bias`` None: of the score), weights ``scale * score /
    sum of the chosen scores``. ``x`` ``(T, D)``, ``router`` ``(D, E)``;
    float32 throughout (``moe_router`` is a FLOAT op). Returns ``chosen
    (T, k)`` int32, ``weights (T, k)``."""
    score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[scoring]
    with jax.named_scope("moe/route"):
        logits = jax.lax.dot_general(
            x.astype(jnp.float32), router.astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        scores = score(logits)
        _, chosen = jax.lax.top_k(
            scores if bias is None else
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        picked = jnp.take_along_axis(scores, chosen, -1)
        weights = scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
        return chosen.astype(jnp.int32), weights


def capacity(rows, top_k, n_experts):
    """Rows a held expert's block holds: ``CAPACITY_FACTOR`` times its even
    share of ``rows * top_k`` assignments, in whole sublanes of 8."""
    share = rows * top_k / n_experts
    return max(8, -(-int(CAPACITY_FACTOR * share) // 8) * 8)


def _swiglu(x, w_gate, w_up, w_down, spec):
    gate = jnp.einsum(spec[0], x, w_gate)
    up = jnp.einsum(spec[0], x, w_up)
    return jnp.einsum(spec[1], jax.nn.silu(gate) * up, w_down)


def _assignments(chosen, held):
    """``hit (T, k, n)``: assignment ``(t, j)`` goes to held expert ``n``."""
    return chosen[..., None] == jnp.asarray(held, jnp.int32)


def expert_load(chosen, held):
    """Rows that reach each held expert, ``(len(held),)`` int32."""
    return jnp.sum(_assignments(chosen, held), (0, 1), dtype=jnp.int32)


def _gathered(x, weights, chosen, w_gate, w_up, w_down, held, cap, over):
    """Each held expert that is not ``over`` on its own rows, compacted into
    ``cap`` rows; ``expert_load <= cap`` wherever ``over`` is False."""
    rows, n = x.shape[0], len(held)
    with jax.named_scope("moe/dispatch"):
        hit = (_assignments(chosen, held) & ~over).reshape(-1, n)
        before = jnp.cumsum(hit, 0, dtype=jnp.int32) - hit      # rank in block
        slot = jnp.sum(jnp.where(hit, before + cap * jnp.arange(n), 0), -1)
        slot = jnp.where(jnp.any(hit, -1), slot, n * cap)       # elsewhere: out
        token = jnp.arange(hit.shape[0], dtype=jnp.int32) // chosen.shape[1]
        # an empty slot reads row 0 under weight 0
        src = jnp.zeros(n * cap, jnp.int32).at[slot].set(token, mode="drop")
        w_slot = jnp.zeros(n * cap, weights.dtype).at[slot].set(
            weights.reshape(-1), mode="drop")
        xs = x[src].reshape(n, cap, -1)
    with jax.named_scope("moe/experts"):
        ys = _swiglu(xs, w_gate, w_up, w_down, ("ecd,edf->ecf", "ecf,efd->ecd"))
    with jax.named_scope("moe/combine"):
        ys = ys.reshape(n * cap, -1).astype(jnp.float32) * w_slot[:, None]
        return jnp.zeros((rows, ys.shape[-1]), jnp.float32).at[src].add(ys)


def _one_expert(x, w_row, gate, up, down):
    out = _swiglu(x, gate, up, down, ("td,df->tf", "tf,fd->td"))
    return out.astype(jnp.float32) * w_row[:, None]


def _row_weights(weights, chosen, held):
    """``(n, T)``: the weight each row gives each held expert, 0 where the
    row did not choose it."""
    return jnp.sum(jnp.where(_assignments(chosen, held), weights[..., None],
                             0.0), 1).T


def _overflowing(chosen, held, cap):
    """``over (n,)`` bool, and the held experts' indices with those that are
    over first, and their number: the turns of the overflow loop."""
    over = expert_load(chosen, held) > cap
    return over, jnp.argsort(~over), jnp.sum(over, dtype=jnp.int32)


def _forward(x, weights, chosen, w_gate, w_up, w_down, held, cap):
    # a rule of the custom_vjp is traced when autodiff gets to it, in or out
    # of the caller's auto_cast: the operands carry the dtypes, not the scope
    from apex_tpu.amp.functional_patch import suspend
    with suspend():
        over, order, n_over = _overflowing(chosen, held, cap)
        y = _gathered(x, weights, chosen, w_gate, w_up, w_down, held, cap,
                      over)
        if cap >= x.shape[0]:       # an expert is chosen once a row at most
            return y
        with jax.named_scope("moe/overflow"):
            rows = _row_weights(weights, chosen, held)

            def one(i, y):          # the i-th overflowing expert, every row
                e = order[i]
                return y + _one_expert(x, rows[e], w_gate[e], w_up[e],
                                       w_down[e])
            return jax.lax.fori_loop(0, n_over, one, y)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _held(x, weights, chosen, w_gate, w_up, w_down, held, cap):
    return _forward(x, weights, chosen, w_gate, w_up, w_down, held, cap)


def _held_fwd(x, weights, chosen, w_gate, w_up, w_down, held, cap):
    return (_forward(x, weights, chosen, w_gate, w_up, w_down, held, cap),
            (x, weights, chosen, w_gate, w_up, w_down))


def _held_bwd(held, cap, res, g):
    from apex_tpu.amp.functional_patch import suspend
    x, weights, chosen, w_gate, w_up, w_down = res
    with suspend():
        over, order, n_over = _overflowing(chosen, held, cap)
        run = lambda x, w, a, b, c: _gathered(x, w, chosen, a, b, c, held,
                                              cap, over)
        d_x, d_w, *d_experts = jax.vjp(run, x, weights, w_gate, w_up,
                                       w_down)[1](g)
        if cap >= x.shape[0]:
            return d_x, d_w, None, *d_experts
        with jax.named_scope("moe/overflow"):
            rows, back = jax.vjp(lambda w: _row_weights(w, chosen, held),
                                 weights)

            def one(i, carry):      # an expert's cotangents into its places
                d_x, d_rows, d_experts = carry
                e = order[i]
                d = jax.vjp(_one_expert, x, rows[e], w_gate[e], w_up[e],
                            w_down[e])[1](g)
                return (d_x + d[0].astype(jnp.float32),
                        d_rows.at[e].set(d[1]),
                        tuple(a.at[e].add(b) for a, b in zip(d_experts, d[2:])))
            more_x, d_rows, d_experts = jax.lax.fori_loop(
                0, n_over, one, (jnp.zeros(x.shape, jnp.float32),
                                 jnp.zeros_like(rows), tuple(d_experts)))
        return (d_x + more_x.astype(x.dtype), d_w + back(d_rows)[0], None,
                *d_experts)


_held.defvjp(_held_fwd, _held_bwd)


def held_experts(x, weights, chosen, w_gate, w_up, w_down, held, n_experts):
    """``sum over the chosen experts i in held of weights_i E_i(x)``, with
    ``E(x) = (silu(x W_gate) * x W_up) W_down``.

    ``x`` ``(T, D)``; ``chosen``, ``weights`` ``(T, k)`` from :func:`route`
    over all ``n_experts``; ``w_gate``, ``w_up`` ``(len(held), D, F)`` and
    ``w_down`` ``(len(held), F, D)`` are the weights of the experts whose
    ids ``held`` lists, in that order. The matmuls run in the ambient
    policy's dtype for ``moe_experts`` (a HALF op), the weighted sum in
    float32. Returns ``(T, D)`` float32; exact for any routing.
    """
    from apex_tpu.amp.policy import current_policy
    dtype = current_policy().op_dtype("moe_experts", x.dtype)
    cast = lambda a: a.astype(dtype)
    return _held(cast(x), weights.astype(jnp.float32), chosen, cast(w_gate),
                 cast(w_up), cast(w_down), tuple(held),
                 capacity(x.shape[0], chosen.shape[1], n_experts))
