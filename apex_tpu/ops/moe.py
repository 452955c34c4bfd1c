"""Routed experts, for a layer that holds some of them.

Under expert parallelism every rank routes each token over *all* experts
and computes the part of the result that its own experts give; the parts
are summed across ranks. These are that rank's two steps: :func:`route`
(scores over every expert, in float32) and :func:`held_experts` (the
weighted sum over the chosen experts that are in ``held``). On one chip
there is no exchange, and nothing here stands in for the absent ranks.

No token is dropped, and a held expert runs on the rows it was sent. Every
assignment of a token to a held expert gets a place in one array of rows
sorted by expert (``moe/dispatch``: a gather of the tokens' rows); the
experts' three matmuls run over it as grouped matmuls (``moe/experts``:
``ops/grouped_matmul.py``, whose kernels visit the tiles that hold a live row
and no other, a number read on the device); then each token's rows are
summed under its weights, in float32 (``moe/combine``): the live rows are
gathered into the tokens' order, where a tile of tokens owns one run of them,
and a sum of rows is a grouped matmul with a 0/1 matrix, the token tiles for
groups. The sort is a bijection between held assignments and live rows, so
every move, forward and backward, is a gather of live rows or that matmul:
there is no ``scatter``. The arrays have a place for every assignment (all
of them may be held), and what XLA does to them between the kernels runs in
chunks under a trip count read on the device (``live_rows``): what a step
costs follows what its routing sent here, and the result does not depend on
it. One ``custom_vjp`` spans the layer: the backward keeps the layer's
inputs and runs the forward again, so that no expert's activations wait for
the backward.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from apex_tpu.ops._dispatch import jit_launcher
from apex_tpu.ops.grouped_matmul import (grouped_matmul, grouped_matmul_t,
                                         row_tile, unwritten, visits)


#: rows of a chunk of the loops over the live rows (``_on_live_rows``)
CHUNK = 2048
#: tokens of a tile of the way back, at most (``_to_tokens``)
TOKEN_TILE = 512


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


def _assignments(chosen, held):
    """``hit (T, k, n)``: assignment ``(t, j)`` goes to held expert ``n``."""
    return chosen[..., None] == jnp.asarray(held, jnp.int32)


def expert_load(chosen, held):
    """Rows that reach each held expert, ``(len(held),)`` int32."""
    return jnp.sum(_assignments(chosen, held), (0, 1), dtype=jnp.int32)


def token_tile(tokens):
    """Tokens of a tile of the way back: a group of the grouped matmul that
    sums the rows of each token (``_to_tokens``)."""
    return min(TOKEN_TILE, -(-tokens // 16) * 16)


class _Back(NamedTuple):
    """The live assignments in the tokens' order (:func:`_sorted`)."""
    row: jax.Array          # (M,) where in the experts' order
    at: jax.Array           # (M,) which token of its tile
    w: jax.Array            # (M,) float32 weight
    per_tile: jax.Array     # (token tiles,) how many


def sorted_rows(assignments):
    """Places in the array of rows sorted by expert: one for every
    assignment, in whole tiles of the grouped matmul."""
    tile = row_tile(assignments)
    return -(-assignments // tile) * tile


def expert_rows_run(load, assignments):
    """Rows the grouped matmul visits for ``load (..., n)`` rows an expert
    out of ``assignments``: visited tiles times a tile's rows. Never under
    ``sum(load)``; over it by what the tiles' edges round up, a tile for
    each expert at most."""
    rows = sorted_rows(assignments)
    return visits(load, rows, row_tile(rows))[3] * row_tile(rows)


def _sorted(chosen, weights, held):
    """Where each held assignment's row is, in the two orders the layer
    needs. By expert, for the matmuls: held expert ``e``'s rows are the
    ``load[e]`` from ``sum(load[:e])`` on, in the tokens' order; live row
    ``r`` is ``token[r]``'s under weight ``w_row[r]``, and assignment ``(t,
    j)`` with ``here[t, j]`` has row ``row[t, j]`` (0 elsewhere). By token,
    for the way back: the live assignments in the tokens' order, the
    ``per_tile[i]`` of token tile ``i`` (:func:`token_tile` tokens) in one
    run; the ``p``-th is the row ``back.row[p]`` of the expert order, of the
    token ``back.at[p]`` of its tile, under weight ``back.w[p]``. Two sorts
    of the assignments' keys; every array has :func:`sorted_rows` places and
    means nothing past the live ones."""
    (t, k), n = chosen.shape, len(held)
    hit = _assignments(chosen, held).reshape(-1, n)
    load = jnp.sum(hit, 0, dtype=jnp.int32)
    rank = jnp.cumsum(hit, 0, dtype=jnp.int32) - hit         # in its expert
    here = jnp.any(hit, -1)
    row = jnp.sum(jnp.where(hit, rank + (jnp.cumsum(load) - load), 0), -1)
    rows = sorted_rows(hit.shape[0])
    pad = lambda a, with_=0: jnp.pad(a, (0, rows - a.shape[0]),
                                     constant_values=with_)
    assignment = jnp.arange(hit.shape[0], dtype=jnp.int32)
    flat = weights.reshape(-1)
    # the live rows' keys are 0, 1, ...: sorted, their tokens are in place
    _, token, w_row = jax.lax.sort(
        (pad(jnp.where(here, row, rows), rows), pad(assignment // k),
         pad(flat)), num_keys=1)
    # the live assignments first, as they come
    tile = token_tile(t)
    _, back_row, back_at, back_w = jax.lax.sort(
        (pad(jnp.where(here, assignment, rows), rows), pad(row),
         pad(assignment // k % tile), pad(flat)), num_keys=1)
    per_tile = jnp.sum(jnp.pad(here, (0, -t % tile * k)).reshape(
        -1, tile * k), -1, dtype=jnp.int32)
    return (load, row.reshape(t, k), here.reshape(t, k), token, w_row,
            _Back(back_row, back_at, back_w, per_tile))


def _on_live_rows(fn, live, *operands):
    """``fn`` on the sorted rows, a chunk of :data:`CHUNK` at a time, as
    many chunks as hold one of the ``live`` rows (a trip count read on the
    device): what XLA does to the sorted rows costs what the routing sent,
    as the kernels do. ``fn`` takes the operands' chunks and returns arrays
    of a chunk's rows; what theirs hold past the last chunk means nothing
    (no pass over all the rows fills them)."""
    rows = operands[0].shape[0]
    # whole tiles of the kernels: a tile they visit lies in a chunk that ran
    tile = row_tile(rows)
    chunk = tile * math.gcd(rows // tile, max(CHUNK // tile, 1))
    at = lambda a, i: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk)
    like = jax.eval_shape(lambda *a: fn(*(at(o, 0) for o in a)), *operands)

    def one(i, outs):
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(out, new, i * chunk, 0)
            for out, new in zip(outs, fn(*(at(o, i) for o in operands))))
    with jax.named_scope("live_rows"):
        return jax.lax.fori_loop(
            0, -(-live // chunk), one,
            tuple(unwritten((rows, *o.shape[1:]), o.dtype) for o in like))


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


def _to_rows(x, token, w_gate, w_up, load):
    """The tokens' rows in the experts' order, and the experts' two first
    matmuls over them."""
    live = jnp.sum(load)
    with jax.named_scope("moe/dispatch"):
        xs, = _on_live_rows(lambda token: (x[token],), live, token)
    with jax.named_scope("moe/experts"):
        gate = grouped_matmul(xs, w_gate, load)
        up = grouped_matmul(xs, w_up, load)
        h, = _on_live_rows(lambda *a: (_swiglu(*a),), live, gate, up)
    return xs, gate, up, h


def _to_tokens(rows, back, tokens, weighted):
    """``(T, D)`` float32: each token's sum over its held assignments of
    their rows of ``rows`` (times their weights if ``weighted``). A sum of
    rows is a matmul with a 0/1 matrix: the live rows are gathered into the
    tokens' order, where a tile of tokens owns one run of them, and
    ``grouped_matmul_t`` with the token tiles for groups multiplies
    ``onehot(token in its tile)^T`` by the run. The weights go into the
    matrix, as their three bfloat16 parts (8 + 8 + 8 bits: a float32
    exactly), so every product is exact and the sums are float32, as a
    gather of ``k`` rows a token and a float32 weighted sum would have them,
    at the cost of the live rows and not of the assignments."""
    tile, dtype = token_tile(tokens), rows.dtype

    def in_order(row, at, w):
        one = at[:, None] == jnp.arange(tile, dtype=jnp.int32)
        parts = []
        for _ in range(3 if weighted else 1):
            part = w.astype(jnp.bfloat16)
            parts.append(jnp.where(one, part[:, None], 0).astype(dtype))
            w = w - part.astype(jnp.float32)
        return (rows[row], *parts)
    mine, *onehot = _on_live_rows(
        in_order, jnp.sum(back.per_tile), back.row, back.at,
        back.w if weighted else jnp.ones_like(back.w))
    sums = grouped_matmul_t(tuple(onehot), mine, back.per_tile, jnp.float32)
    return sums.reshape(-1, rows.shape[1])[:tokens]


# The two rules are jitted: a decoder has the layer several times over, at
# one shape, and a trace of it holds eight loops and seventeen kernels.

@jit_launcher(static_argnums=(6,))
def _forward(x, weights, chosen, w_gate, w_up, w_down, held):
    # a rule of the custom_vjp is traced when autodiff gets to it, in or out
    # of the caller's auto_cast: the operands carry the dtypes, not the scope
    from apex_tpu.amp.functional_patch import suspend
    with suspend():
        with jax.named_scope("moe/dispatch"):
            load, _, _, token, _, back = _sorted(chosen, weights, held)
        h = _to_rows(x, token, w_gate, w_up, load)[3]
        with jax.named_scope("moe/experts"):
            ys = grouped_matmul(h, w_down, load)
        with jax.named_scope("moe/combine"):
            return _to_tokens(ys, back, x.shape[0], weighted=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _held(x, weights, chosen, w_gate, w_up, w_down, held):
    return _forward(x, weights, chosen, w_gate, w_up, w_down, held)


def _held_fwd(x, weights, chosen, w_gate, w_up, w_down, held):
    return (_forward(x, weights, chosen, w_gate, w_up, w_down, held),
            (x, weights, chosen, w_gate, w_up, w_down))


@jit_launcher(static_argnums=(0,))
def _held_bwd(held, res, g):
    from apex_tpu.amp.functional_patch import suspend
    x, weights, chosen, w_gate, w_up, w_down = res
    with suspend():
        with jax.named_scope("moe/dispatch"):
            load, row, here, token, w_row, back = _sorted(chosen, weights,
                                                          held)
        live = jnp.sum(load)
        xs, gate, up, h = _to_rows(x, token, w_gate, w_up, load)
        with jax.named_scope("moe/experts"):
            ys = grouped_matmul(h, w_down, load)
        with jax.named_scope("moe/combine"):
            def of_rows(token, w_row, ys):  # of y = sum w_row ys, a row
                g_rows = g[token]
                return ((g_rows * w_row[:, None]).astype(ys.dtype),
                        jnp.sum(g_rows * ys.astype(jnp.float32), -1))
            d_ys, d_w_row = _on_live_rows(of_rows, live, token, w_row, ys)
            d_w = jnp.where(here, d_w_row[row], 0.0)
        with jax.named_scope("moe/experts"):
            d_h = grouped_matmul(d_ys, w_down, load, transposed=True)
            d_gate, d_up = _on_live_rows(
                lambda gate, up, d_h: jax.vjp(_swiglu, gate, up)[1](d_h),
                live, gate, up, d_h)
            d_xs = grouped_matmul((d_gate, d_up), (w_gate, w_up), load,
                                  transposed=True)
            d_experts = (grouped_matmul_t(xs, d_gate, load),
                         grouped_matmul_t(xs, d_up, load),
                         grouped_matmul_t(h, d_ys, load))
        with jax.named_scope("moe/dispatch"):
            d_x = _to_tokens(d_xs, back, x.shape[0],
                             weighted=False).astype(x.dtype)
        return d_x, d_w, None, *d_experts


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
    del n_experts       # the cost follows the rows sent here, whatever share
    from apex_tpu.amp.policy import current_policy
    dtype = current_policy().op_dtype("moe_experts", x.dtype)
    cast = lambda a: a.astype(dtype)
    return _held(cast(x), weights.astype(jnp.float32), chosen, cast(w_gate),
                 cast(w_up), cast(w_down), tuple(held))
