"""Gated delta rule with a decay for each key channel (or one a head), in
chunks.

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

The op has its own backward (``jax.custom_vjp``). Head sizes of whole
128-lane tiles (the published KDA and gated-DeltaNet sizes) take two Pallas
kernels further down, ``apex_kda_fwd`` and ``apex_kda_bwd`` for a decay a
key channel, ``apex_gdn_fwd`` and ``apex_gdn_bwd`` for one a head: all
three parts a chunk at a time, chunks in order, the state in VMEM, so that
the score matrices, the solve, the chunk's terms and every cotangent of
them never reach HBM. From the forward to the backward they keep the
inputs, the chunk-start states and ``(I + A)^-1`` (``C`` floats a token and
head). Any other head size takes the three parts as ``jax.numpy``,
``HEAD_GROUP`` heads at a time, and keeps the inputs and the states; its
backward runs ``_prepare`` again. What the op sees of its inputs' shapes
picks the path; no argument does.

Under recomputation. The forward rule names what it hands the backward
beside the inputs (the output, the states and, from the kernel, ``(I +
A)^-1``) ``ops.KEPT_KDA`` by ``jax.ad_checkpoint.checkpoint_name``: a
``jax.checkpoint`` or ``nn.remat`` round the op with
``policy=jax.checkpoint_policies.save_only_these_names(*ops.KEPT_NAMES)``
keeps them, and its rerun of the forward then holds no ``apex_kda_fwd``.
Outside a checkpoint the name is the identity and lowers to nothing.

Decay. ``g <= 0`` is the log of the decay, and ``G`` its running sum from
the chunk's start. ``exp(G_t - G_s)`` for ``s <= t`` is at most 1, but the
factorisation ``exp(G_t) * exp(-G_s)`` that turns it into a matmul is not:
at ``g = -5`` a step ``exp(-G_s)`` passes float32 after 18 tokens. So the
chunk is cut into sub-blocks of ``SUB``: between two sub-blocks the sum is
split at the later one's start, ``exp(G_t - r) * exp(r - G_s)`` with both
exponents <= 0, and inside a sub-block the ``(SUB, SUB, d_k)`` terms are
summed as they are (the kernels split at the middle of every block of 2, 4,
... ``CHUNK`` tokens instead, see there). Nothing that can overflow is
formed; what underflows is a contribution that is zero in float32 anyway.
With one decay a head (``g`` of ``(B, T, H)``) the kernels take ``g`` as it
is: a split's two exponents are one number a token each, so the ``(C, C)``
decay matrix is built from their outer products on the vector unit, still
from sums of ``g`` that are ``<= 0``, and the scores are one matmul times
it; ``d g`` comes back ``(B, T, H)``. The ``jax.numpy`` form broadcasts a
decay a head over the key channels.

Precision. State, decay and the solve are float32 whatever the inputs
(``gated_delta_rule`` is a FLOAT op of ``amp/lists.py``); the matmuls take
float32 operands at the backend's default precision, the ``(CHUNK,
CHUNK)`` matrices that feed the solve, and the solve, at ``HIGHEST``; on
both paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.ops._dispatch import KEPT_KDA, jit_launcher, pallas_call

CHUNK = 64
SUB = 16
#: heads that go through the ``jax.numpy`` form together: its backward's
#: working set is ~110 MB a head at 8192 tokens; the groups run in turn
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


# ---- the Pallas kernels: a chunk of a few heads a grid step -----------------
#
# A pair ``s < t`` of a chunk differs in one highest bit ``L`` of its two
# positions: ``t`` lies in the upper half and ``s`` in the lower half of one
# block of ``2 L`` tokens. Level ``L`` takes ``G`` at that block's middle as
# the point to split the sum at, for all its blocks at once: one exponent
# ``n_L <= 0`` a token (the sum of ``g`` between the token and the middle),
# and one masked ``(C, d) x (d, C)`` matmul. ``log2 C`` levels cover every
# pair once, so nothing is summed on the vector unit and no ``(SUB, SUB, d)``
# term is formed; ``SUB`` plays no part here. The exponents themselves come
# from one matmul of a constant 0/1 matrix with ``g``: sums of ``g``, never
# differences of ``G``. ``(I + A)^-1`` is built level by level beside them:
# the inverse of a block of ``2 L`` from those of its halves (block forward
# substitution, two matmuls a level), kept whole for the backward.
#
# The chunks of ``HEADS_A_STEP`` heads go through as one: their rows stacked,
# ``(heads C, d)``, under the same masks, which keep the heads apart because
# no level reaches across ``C``. A dependent chain of small matmuls waits on
# the matrix unit's latency once for all of them, and a grid step's fixed
# cost is shared. Where a step's heads are exactly one key head's group, the
# kernels of one decay a head read that key head's block by the index map
# and stack it once a head; the backward sums the heads' cotangents of it
# before it writes the block, so a key head has one writer.

HEADS_A_STEP = 2


def _step_heads(h):
    """Heads a grid step takes of ``h``."""
    return HEADS_A_STEP if h % HEADS_A_STEP == 0 else 1


def _levels(c):
    return tuple(1 << i for i in range(c.bit_length() - 1))


@functools.lru_cache(None)
def _sum_matrix(c, heads):
    """``(2 + log2 C) heads C, heads C`` of 0/1: stacked, the rows that sum
    ``g`` into ``G`` (inclusive), into ``G_end - G`` and into each level's
    exponent, each block-diagonal over the heads."""
    t, s = np.arange(c)[:, None], np.arange(c)[None, :]
    blocks = [s <= t, s > t]
    for lv in _levels(c):
        mid = t // (2 * lv) * (2 * lv) + lv - 1      # last token of the lower half
        blocks.append(np.where(t > mid, (s > mid) & (s <= t),
                               (s > t) & (s <= mid)))
    return np.concatenate([np.kron(np.eye(heads), b) for b in blocks],
                          0).astype(np.float32)


def _dot(a, b, contract=((1,), (0,)), precision=_HI):
    return lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


_mm = functools.partial(_dot, precision=None)   # the state and output products
_NT = ((1,), (1,))                      # a @ b.T
_TN = ((0,), (0,))                      # a.T @ b


def _sums(matrix, x):
    """``matrix @ x`` in float32 for a 0/1 ``matrix`` (exact in bfloat16):
    the three bfloat16 pieces of ``x``, one pass each, where ``HIGHEST``
    would make six. The passes name their precision: under a
    ``jax.default_matmul_precision`` of ``highest`` a bfloat16 product with
    none would ask Mosaic for float32 passes, which it refuses."""
    out = 0.0
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        out = out + _dot(matrix, piece, precision=lax.Precision.DEFAULT)
        x = x - piece.astype(jnp.float32)
    return out


def _grown(inverse, a, i, eye):
    """``(I + A)^-1`` of blocks of ``2 L`` from that of blocks of ``L``
    (``inverse``) and level ``i``'s part of ``A`` (``a``): block forward
    substitution, two matmuls; at level 1 the blocks of one are ``I``."""
    return inverse - _dot(_dot(inverse, a), inverse) if i else eye - a


def _chunk_forward(q, k, v, g, beta, sum_matrix, inverse=None):
    """One chunk of a few heads, rows stacked: ``q, k, g`` ``(R, d_k)``, ``v``
    ``(R, d_v)``, ``beta`` ``(R, 1)``, float32, ``R`` = heads x ``CHUNK``;
    ``sum_matrix`` in bfloat16. Returns ``qg, kg, w, ut, aqk, from_start``
    (``aqk`` ``(R, R)``, a head's block on the diagonal; ``decay`` is the last
    row of a head's ``from_start``) and what the backward needs of the way
    there. ``inverse``: ``(I + A)^-1`` where the caller has it already."""
    r, dk = k.shape
    sums = jnp.minimum(_sums(sum_matrix, g), 0.0)
    part = lambda i: sums[i * r:(i + 1) * r]
    from_start, to_end = jnp.exp(part(0)), jnp.exp(part(1))
    row = lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    # q's rows, then k's, against the columns: the bits two tokens differ in
    differ = (lax.broadcasted_iota(jnp.int32, (2 * r, r), 0)
              ^ lax.broadcasted_iota(jnp.int32, (2 * r, r), 1)) & (r - 1)
    eye = (differ[:r] == 0).astype(k.dtype)
    scores = jnp.zeros((2 * r, r), k.dtype)
    build = inverse is None
    kept = []
    for i, lv in enumerate(_levels(CHUNK)):
        e = jnp.exp(part(2 + i))
        upper = (row & lv) != 0
        e_near, e_far = jnp.where(upper, e, 0.0), jnp.where(upper, 0.0, e)
        near = jnp.concatenate([q * e_near, k * e_near], 0)
        far = k * e_far
        level = jnp.where(differ < 2 * lv, _dot(near, far, _NT), 0.0)
        scores = scores + level
        if build:
            inverse = _grown(inverse, level[r:] * beta, i, eye)
        kept.append((e_near, e_far, near, far))
    aqk = scores[:r] + eye * jnp.sum(q * k, -1, keepdims=True)
    k_start = k * from_start
    rhs = jnp.concatenate([k_start, v], 1) * beta
    solved = _dot(inverse, rhs)
    out = (q * from_start, k * to_end, solved[:, :dk], solved[:, dk:], aqk,
           from_start)
    return out, (to_end, k_start, scores[r:], inverse, solved, kept, differ,
                 eye)


def _chunk_backward(q, k, v, beta, sum_matrix_t, forward, cts):
    """Cotangents of ``q, k, v, g, beta`` for those of ``qg, kg, w, ut, aqk``
    and of ``from_start`` (``decay``'s, in a head's last row); ``forward``:
    what ``_chunk_forward`` returned."""
    d_qg, d_kg, d_w, d_ut, d_aqk, d_from_start = cts
    r, dk = k.shape
    (_, kg, _, _, _, from_start), (
        to_end, k_start, skk, inverse, solved, kept, differ, eye) = forward
    # the solve: solved = inverse @ rhs, inverse = (I + beta * skk)^-1
    d_rhs = _dot(_dot(eye, inverse, _NT), jnp.concatenate([d_w, d_ut], 1))
    d_a = -_dot(d_rhs, solved, _NT)
    d_beta = (jnp.sum(d_rhs[:, :dk] * k_start, -1, keepdims=True)
              + jnp.sum(d_rhs[:, dk:] * v, -1, keepdims=True)
              + jnp.sum(d_a * skk, -1, keepdims=True))
    d_rhs = d_rhs * beta
    d_v = d_rhs[:, dk:]
    on_diagonal = jnp.sum(d_aqk * eye, -1, keepdims=True)
    d_q = d_qg * from_start + on_diagonal * k
    d_k = d_rhs[:, :dk] * from_start + d_kg * to_end + on_diagonal * q
    d_sums = [(d_qg * q + d_rhs[:, :dk] * k + d_from_start) * from_start,
              d_kg * kg]
    d_scores = jnp.concatenate([d_aqk, d_a * beta], 0)
    # the transposed cotangents, q's beside k's: (R, 2 R)
    d_scores_t = jnp.concatenate([_dot(eye, d_aqk, _NT),
                                  _dot(eye, d_a * beta, _NT)], 1)
    differ_t = jnp.concatenate([differ[:r], differ[:r]], 1)
    for lv, (e_near, e_far, near, far) in zip(_levels(CHUNK), kept):
        d_near = _dot(jnp.where(differ < 2 * lv, d_scores, 0.0), far)
        d_far = _dot(jnp.where(differ_t < 2 * lv, d_scores_t, 0.0), near)
        d_q = d_q + d_near[:r] * e_near
        d_k = d_k + d_near[r:] * e_near + d_far * e_far
        d_sums.append(d_near[:r] * near[:r] + d_near[r:] * near[r:]
                      + d_far * far)
    d_g = _sums(sum_matrix_t, jnp.concatenate(d_sums, 0))
    return d_q, d_k, d_v, d_g, d_beta


# One decay a head (gated DeltaNet): a level's exponents are one number a
# token, so a level's matrix is ``q k^T`` times the outer product of its
# ``e_near`` and ``e_far`` columns. The six outer products, each under its
# level's mask, make one decay matrix ``D`` on the vector unit (``D[t, s] =
# exp(G_t - G_s)`` for ``s < t`` in a head, as a product of two factors
# ``<= 1``; zero elsewhere), and the scores are one matmul ``[q; k] k^T``
# times ``D``. The level masks still cut ``A`` for the inverse's chain.


def _gdn_chunk_forward(q, k, v, g, beta, sum_matrix, inverse=None):
    """``_chunk_forward`` for one decay a head: ``g`` ``(R, 1)`` like
    ``beta``; ``from_start`` comes back ``(R, 1)``."""
    r, dk = k.shape
    # every lane of a row holds its token's sums: the transpose of a level's
    # block is then its exponents along the columns
    sums = jnp.minimum(_sums(sum_matrix, jnp.broadcast_to(g, (r, r))), 0.0)
    part = lambda i: sums[i * r:(i + 1) * r]
    from_start, to_end = (jnp.exp(part(i)[:, :1]) for i in (0, 1))
    row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    differ = row ^ col              # the bits two tokens differ in
    eye = (differ == 0).astype(k.dtype)
    decay = jnp.zeros((r, r), k.dtype)
    for i in range(len(_levels(CHUNK))):
        # the pairs whose highest differing bit is level i's: exp of the sum
        # from s to the block's middle times exp of the one from there to t
        e = jnp.exp(part(2 + i))
        decay = jnp.where((row > col) & (differ >> i == 1), e * e.T, decay)
    qk = jnp.concatenate([q, k], 0)
    scores = _dot(qk, k, _NT) * jnp.concatenate([decay, decay], 0)
    if inverse is None:
        for i in range(len(_levels(CHUNK))):
            inverse = _grown(inverse, jnp.where(differ >> i == 1, scores[r:],
                                                0.0) * beta, i, eye)
    aqk = scores[:r] + eye * jnp.sum(q * k, -1, keepdims=True)
    k_start = k * from_start
    rhs = jnp.concatenate([k_start, v], 1) * beta
    solved = _dot(inverse, rhs)
    out = (q * from_start, k * to_end, solved[:, :dk], solved[:, dk:], aqk,
           from_start)
    return out, (to_end, k_start, scores, inverse, solved, qk, decay, differ,
                 eye)


def _gdn_chunk_backward(q, k, v, beta, sum_matrix_t, forward, cts):
    """``_chunk_backward`` for one decay a head: ``d g`` ``(R, 1)``."""
    d_qg, d_kg, d_w, d_ut, d_aqk, d_from_start = cts
    r, dk = k.shape
    (_, kg, _, _, _, from_start), (
        to_end, k_start, scores, inverse, solved, qk, decay, differ,
        eye) = forward
    # the solve: solved = inverse @ rhs, inverse = (I + beta * skk)^-1
    d_rhs = _dot(_dot(eye, inverse, _NT), jnp.concatenate([d_w, d_ut], 1))
    d_a = -_dot(d_rhs, solved, _NT)
    d_beta = (jnp.sum(d_rhs[:, :dk] * k_start, -1, keepdims=True)
              + jnp.sum(d_rhs[:, dk:] * v, -1, keepdims=True)
              + jnp.sum(d_a * scores[r:], -1, keepdims=True))
    d_rhs = d_rhs * beta
    d_v = d_rhs[:, dk:]
    on_diagonal = jnp.sum(d_aqk * eye, -1, keepdims=True)
    d_scores = jnp.concatenate([d_aqk, d_a * beta], 0)
    # scores = ([q; k] k^T) * D: one matmul for [q; k] on the left, one for
    # k on the right
    d_qk = d_scores * jnp.concatenate([decay, decay], 0)
    d_near = _dot(d_qk, k)
    d_far = _dot(d_qk, qk, _TN)
    d_q = d_qg * from_start + on_diagonal * k + d_near[:r]
    d_k = (d_rhs[:, :dk] * from_start + d_kg * to_end + on_diagonal * q
           + d_near[r:] + d_far)
    # a pair's term is exp(x_t) exp(x_s) of its level: its cotangent times
    # itself goes to both exponents, row t's and column s's
    d_terms = d_scores[:r] * scores[:r] + d_scores[r:] * scores[r:]
    d_terms = d_terms + d_terms.T
    d_sums = [(jnp.sum(d_qg * q + d_rhs[:, :dk] * k, -1, keepdims=True)
               + d_from_start) * from_start,
              jnp.sum(d_kg * kg, -1, keepdims=True)]
    d_sums += [jnp.sum(jnp.where(differ >> i == 1, d_terms, 0.0), -1,
                       keepdims=True) for i in range(len(_levels(CHUNK)))]
    d_g = _sums(sum_matrix_t, jnp.concatenate(d_sums, 0))
    return d_q, d_k, d_v, d_g, d_beta


# The recurrence runs in the same kernels: the chunk axis is the grid's last,
# taken in order (in reverse by the backward), and the ``(d_k, d_v)`` state
# of each head (the backward's: its cotangent) is carried in a VMEM scratch.
# So ``qg, kg, w, ut, aqk`` never reach HBM: the forward writes the output
# in the model's layout, the chunk-start states and ``(I + A)^-1``, and the
# backward reads those, the inputs and ``d out``. The state and output
# products take float32 operands at the default precision, as ``_chunk_step``
# and ``_output`` below do.

def _stacked(ref, heads):
    """A ``(C, heads d)`` block, the heads side by side, as ``(heads C, d)``
    float32, one head's rows after another's."""
    d = ref.shape[-1] // heads
    return jnp.concatenate([ref[:, j * d:(j + 1) * d].astype(jnp.float32)
                            for j in range(heads)], 0)


def _columns(ref, first, heads):
    """Columns ``first ... first + heads`` of a ``(C, H)`` block, stacked:
    ``(heads C, 1)`` float32."""
    block = ref[...].astype(jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.concatenate([
        jnp.sum(jnp.where(lane == first + j, block, 0.0), -1, keepdims=True)
        for j in range(heads)], 0)


def _diagonal_blocks(blocks):
    """``(C, C)`` blocks on the diagonal of one ``(n C, n C)`` matrix."""
    zero = jnp.zeros_like(blocks[0])
    return jnp.concatenate([
        jnp.concatenate([b if i == j else zero for j in range(len(blocks))], 1)
        for i, b in enumerate(blocks)], 0)


def _turned(x, eye):
    """A ``(1, d)`` row as a ``(d, 1)`` column, or back: through the
    diagonal of ``eye``, ``(d, d)``."""
    return jnp.sum(eye * x, -1 if x.shape[0] == 1 else 0, keepdims=True)


def _eye(d):
    return (lax.broadcasted_iota(jnp.int32, (d, d), 0)
            == lax.broadcasted_iota(jnp.int32, (d, d), 1)).astype(jnp.float32)


def _keys(ref, heads, shared):
    """``q`` or ``k`` as ``(heads C, d)`` rows: ``_stacked``, or one key
    head's ``(C, d)`` block once for each of the step's value heads."""
    if not shared:
        return _stacked(ref, heads)
    return jnp.concatenate([ref[...].astype(jnp.float32)] * heads, 0)


def _steps(heads, terms, inverse, turn, out_ref, states_ref, inverse_ref,
           state):
    """Each head's state step and output from its chunk's terms, and what
    the forward keeps. ``turn``: a head's last row of ``from_start`` as the
    decay of its state's rows (a ``(d_k, 1)`` column, or the one number)."""
    qg, kg, w, ut, aqk, from_start = terms
    c, dv = CHUNK, ut.shape[-1]
    for j in range(heads):
        rows = slice(j * c, (j + 1) * c)
        start = state[j]
        states_ref[j] = start
        inverse_ref[j] = inverse[rows, rows]
        u = ut[rows] - _mm(w[rows], start)
        out_ref[:, j * dv:(j + 1) * dv] = (
            _mm(qg[rows], start) + _mm(aqk[rows, rows], u))
        decay = turn(from_start[(j + 1) * c - 1:(j + 1) * c])
        state[j] = decay * start + _mm(kg[rows], u, _TN)


def _steps_backward(heads, terms, turn, states_ref, d_out_ref, lam_ref):
    """The cotangents of each head's ``qg, kg, w, ut, aqk`` and of its
    decay (in its last row) from ``d out`` and the state's, which runs back
    through ``lam_ref``; ``turn`` as in :func:`_steps`, and back."""
    qg, kg, w, ut, aqk, from_start = terms
    c, dv = CHUNK, ut.shape[-1]
    last = lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    cts = []
    for j in range(heads):
        # o = qg S + aqk u, u = ut - w S, S' = decay S + kg^T u: lam is S''s
        rows = slice(j * c, (j + 1) * c)
        start, lam = states_ref[j], lam_ref[j]
        d_o = d_out_ref[:, j * dv:(j + 1) * dv].astype(jnp.float32)
        block = aqk[rows, rows]
        u = ut[rows] - _mm(w[rows], start)
        d_u = _mm(block, d_o, _TN) + _mm(kg[rows], lam)
        decay = from_start[(j + 1) * c - 1:(j + 1) * c]
        d_decay = turn(jnp.sum(start * lam, -1, keepdims=True))
        cts.append((_mm(d_o, start, _NT), _mm(u, lam, _NT),
                    -_mm(d_u, start, _NT), d_u, _mm(d_o, u, _NT),
                    jnp.where(last, d_decay, 0.0)))
        lam_ref[j] = (_mm(qg[rows], d_o, _TN) + turn(decay) * lam
                      - _mm(w[rows], d_u, _TN))
    return zip(*cts)


def _whole(x):
    """``turn`` for one decay a head: a ``(1, 1)`` decay, or a ``(d_k, 1)``
    column of the state's rows summed, as one number (a scalar: Mosaic
    broadcasts no ``(1, 1)`` block both ways)."""
    return jnp.sum(x)


def _fwd_kernel(heads, q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref,
                out_ref, states_ref, inverse_ref, state):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, k, v, g = (_stacked(r, heads) for r in (q_ref, k_ref, v_ref, g_ref))
    beta = _columns(beta_ref, pl.program_id(1) * heads, heads)
    terms, kept = _chunk_forward(q, k, v, g, beta, sums_ref[...])
    eye = _eye(k.shape[-1])
    _steps(heads, terms, kept[3], lambda x: _turned(x, eye), out_ref,
           states_ref, inverse_ref, state)


def _gdn_fwd_kernel(heads, shared, q_ref, k_ref, v_ref, g_ref, beta_ref,
                    sums_ref, out_ref, states_ref, inverse_ref, state):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    q, k = (_keys(r, heads, shared) for r in (q_ref, k_ref))
    v = _stacked(v_ref, heads)
    g, beta = (_columns(r, pl.program_id(1) * heads, heads)
               for r in (g_ref, beta_ref))
    terms, kept = _gdn_chunk_forward(q, k, v, g, beta, sums_ref[...])
    _steps(heads, terms, kept[3], _whole, out_ref, states_ref, inverse_ref,
           state)


def _bwd_kernel(heads, q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref,
                sums_t_ref, states_ref, inverse_ref, d_out_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, dbeta_ref, lam_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        lam_ref[...] = jnp.zeros_like(lam_ref)

    q, k, v, g = (_stacked(r, heads) for r in (q_ref, k_ref, v_ref, g_ref))
    beta = _columns(beta_ref, pl.program_id(1) * heads, heads)
    forward = _chunk_forward(
        q, k, v, g, beta, sums_ref[...],
        _diagonal_blocks([inverse_ref[j] for j in range(heads)]))
    c = CHUNK
    eye = _eye(k.shape[-1])
    d_qg, d_kg, d_w, d_ut, d_aqk, d_from_start = _steps_backward(
        heads, forward[0], lambda x: _turned(x, eye), states_ref, d_out_ref,
        lam_ref)
    *grads, d_beta = _chunk_backward(
        q, k, v, beta, sums_t_ref[...], forward,
        tuple(jnp.concatenate(x, 0) for x in (d_qg, d_kg, d_w, d_ut))
        + (_diagonal_blocks(d_aqk), jnp.concatenate(d_from_start, 0)))
    for ref, x in zip((dq_ref, dk_ref, dv_ref, dg_ref), grads):
        d = x.shape[-1]
        for j in range(heads):
            ref[:, j * d:(j + 1) * d] = x[j * c:(j + 1) * c].astype(ref.dtype)
    for j in range(heads):
        dbeta_ref[j] = d_beta[j * c:(j + 1) * c]


def _gdn_bwd_kernel(heads, shared, q_ref, k_ref, v_ref, g_ref, beta_ref,
                    sums_ref, sums_t_ref, states_ref, inverse_ref, d_out_ref,
                    dq_ref, dk_ref, dv_ref, dgb_ref, lam_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        lam_ref[...] = jnp.zeros_like(lam_ref)

    q, k = (_keys(r, heads, shared) for r in (q_ref, k_ref))
    v = _stacked(v_ref, heads)
    g, beta = (_columns(r, pl.program_id(1) * heads, heads)
               for r in (g_ref, beta_ref))
    forward = _gdn_chunk_forward(
        q, k, v, g, beta, sums_ref[...],
        _diagonal_blocks([inverse_ref[j] for j in range(heads)]))
    c = CHUNK
    d_qg, d_kg, d_w, d_ut, d_aqk, d_from_start = _steps_backward(
        heads, forward[0], _whole, states_ref, d_out_ref, lam_ref)
    d_q, d_k, d_v, d_g, d_beta = _gdn_chunk_backward(
        q, k, v, beta, sums_t_ref[...], forward,
        tuple(jnp.concatenate(x, 0) for x in (d_qg, d_kg, d_w, d_ut))
        + (_diagonal_blocks(d_aqk), jnp.concatenate(d_from_start, 0)))
    head = lambda x, j: x[j * c:(j + 1) * c]
    by_head = [(dq_ref, d_q), (dk_ref, d_k), (dv_ref, d_v)]
    if shared:      # the step's heads are the key head's group: one writer
        for ref, x in by_head[:2]:
            ref[...] = functools.reduce(
                jnp.add, (head(x, j) for j in range(heads))).astype(ref.dtype)
        by_head = by_head[2:]
    for ref, x in by_head:
        d = x.shape[-1]
        for j in range(heads):
            ref[:, j * d:(j + 1) * d] = head(x, j).astype(ref.dtype)
    for j in range(heads):
        dgb_ref[j] = jnp.concatenate([head(d_g, j), head(d_beta, j)], 1)


def _tiled(dk, dv):
    """Whether the kernels take these head sizes: whole 128-lane tiles."""
    return dk % 128 == 0 and dv % 128 == 0


def _specs(q, v, beta, reverse=False, group=1):
    """Grid and block specs of the kernels: ``(batch, heads, chunk)``, the
    chunks in order (``reverse``: last first); the model's ``(B, T, H d)``
    layout for tokens, chunk-major ``(N, B, H, ., .)`` for the rest.
    ``inputs``: the per-channel kernels'; ``gdn_inputs``: those of one decay
    a head, ``g`` read as ``beta`` is and ``q, k`` at the step's key head
    where the step's heads are one key head's ``group``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, t, h = beta.shape
    dk, dv, chunks, c = (q.shape[-1] * group // h, v.shape[-1] // h,
                         t // CHUNK, CHUNK)
    heads = _step_heads(h)
    at = (lambda n: chunks - 1 - n) if reverse else (lambda n: n)
    token = lambda d: pl.BlockSpec((None, c, heads * d),
                                   lambda b, h, n: (b, at(n), h))
    chunk = lambda r, d: pl.BlockSpec((None, None, heads, r, d),
                                      lambda b, h, n: (at(n), b, h, 0, 0))
    column = pl.BlockSpec((None, c, h), lambda b, h, n: (b, at(n), 0))
    keys = pl.BlockSpec((None, c, heads * dk // group),
                        lambda b, h, n: (b, at(n), h))
    sums = _sum_matrix(c, heads)
    return dict(
        heads=heads, dk=dk, dv=dv, grid=(b, h // heads, chunks), token=token,
        chunk=chunk,
        inputs=[token(dk), token(dk), token(dv), token(dk), column],
        gdn_inputs=[keys, keys, token(dv), column, column],
        per_chunk=lambda r, d: jax.ShapeDtypeStruct((chunks, b, h, r, d),
                                                    jnp.float32),
        sums=jnp.asarray(sums, jnp.bfloat16),
        sums_t=jnp.asarray(sums.T, jnp.bfloat16),
        whole=lambda x: pl.BlockSpec(x.shape, lambda b, h, n: (0, 0)),
        state=pltpu.VMEM((heads, dk, dv), jnp.float32),
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")))


# Both launchers are jitted: a model's layers of one shape share one trace.

@jit_launcher
def _forward_kernel(q, k, v, g, beta):
    """The op by the forward kernel, ``q, k, v, g`` as ``(B, T, H d)``: the
    output ``(B, T, H, d_v)``, the chunk-start states ``(N, B, H, d_k, d_v)``
    and ``(I + A)^-1`` ``(N, B, H, C, C)``, both for the backward kernel."""
    sp = _specs(q, v, beta)
    (b, t, h), dk, dv, c = beta.shape, sp["dk"], sp["dv"], CHUNK
    out, states, inverse = pallas_call(
        functools.partial(_fwd_kernel, sp["heads"]),
        name="apex_kda_fwd", grid=sp["grid"],
        in_specs=sp["inputs"] + [sp["whole"](sp["sums"])],
        out_specs=[sp["token"](dv), sp["chunk"](dk, dv), sp["chunk"](c, c)],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), jnp.float32),
                   sp["per_chunk"](dk, dv), sp["per_chunk"](c, c)],
        scratch_shapes=[sp["state"]], compiler_params=sp["params"],
    )(q, k, v, g, beta, sp["sums"])
    return out.reshape(b, t, h, dv), states, inverse


@jit_launcher
def _backward_kernel(q, k, v, g, beta, states, inverse, d_out):
    """Cotangents of the op's inputs, in their layout and dtypes."""
    sp = _specs(q, v, beta, reverse=True)
    (b, t, h), dk, dv, c = beta.shape, sp["dk"], sp["dv"], CHUNK
    *grads, d_beta = pallas_call(
        functools.partial(_bwd_kernel, sp["heads"]),
        name="apex_kda_bwd", grid=sp["grid"],
        in_specs=sp["inputs"] + [
            sp["whole"](sp["sums"]), sp["whole"](sp["sums_t"]),
            sp["chunk"](dk, dv), sp["chunk"](c, c), sp["token"](dv)],
        out_specs=sp["inputs"][:4] + [sp["chunk"](c, 1)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g)] + [sp["per_chunk"](c, 1)],
        scratch_shapes=[sp["state"]], compiler_params=sp["params"],
    )(q, k, v, g, beta, sp["sums"], sp["sums_t"], states, inverse,
      d_out.reshape(b, t, h * dv))
    d_beta = jnp.transpose(d_beta[..., 0], (1, 0, 3, 2)).reshape(b, t, h)
    return (*grads, d_beta.astype(beta.dtype))


@jit_launcher(static_argnums=(5,))
def _gdn_forward_kernel(q, k, v, g, beta, group):
    """``_forward_kernel`` for one decay a head: ``g`` ``(B, T, H)``; ``q, k``
    ``(B, T, H d_k / group)``, ``group`` 1 or the heads of a grid step."""
    sp = _specs(q, v, beta, group=group)
    (b, t, h), dk, dv, c = beta.shape, sp["dk"], sp["dv"], CHUNK
    out, states, inverse = pallas_call(
        functools.partial(_gdn_fwd_kernel, sp["heads"], group > 1),
        name="apex_gdn_fwd", grid=sp["grid"],
        in_specs=sp["gdn_inputs"] + [sp["whole"](sp["sums"])],
        out_specs=[sp["token"](dv), sp["chunk"](dk, dv), sp["chunk"](c, c)],
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), jnp.float32),
                   sp["per_chunk"](dk, dv), sp["per_chunk"](c, c)],
        scratch_shapes=[sp["state"]], compiler_params=sp["params"],
    )(q, k, v, g, beta, sp["sums"])
    return out.reshape(b, t, h, dv), states, inverse


@jit_launcher(static_argnums=(8,))
def _gdn_backward_kernel(q, k, v, g, beta, states, inverse, d_out, group):
    """Cotangents of ``_gdn_forward_kernel``'s inputs, in their layout and
    dtypes: a key head's from the one grid step that reads it."""
    sp = _specs(q, v, beta, reverse=True, group=group)
    (b, t, h), dk, dv, c = beta.shape, sp["dk"], sp["dv"], CHUNK
    *grads, d_gb = pallas_call(
        functools.partial(_gdn_bwd_kernel, sp["heads"], group > 1),
        name="apex_gdn_bwd", grid=sp["grid"],
        in_specs=sp["gdn_inputs"] + [
            sp["whole"](sp["sums"]), sp["whole"](sp["sums_t"]),
            sp["chunk"](dk, dv), sp["chunk"](c, c), sp["token"](dv)],
        out_specs=sp["gdn_inputs"][:3] + [sp["chunk"](c, 2)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v)] + [sp["per_chunk"](c, 2)],
        scratch_shapes=[sp["state"]], compiler_params=sp["params"],
    )(q, k, v, g, beta, sp["sums"], sp["sums_t"], states, inverse,
      d_out.reshape(b, t, h * dv))
    d_g, d_beta = (jnp.transpose(d_gb[..., i], (1, 0, 3, 2)).reshape(b, t, h)
                   for i in (0, 1))
    return (*grads, d_g.astype(g.dtype), d_beta.astype(beta.dtype))


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


def _forward(q, k, v, g, beta, group):
    """The output ``(B, T, H, d_v)`` and what the backward keeps beside the
    inputs: the chunk-start states and, from the kernel, ``(I + A)^-1``.
    ``group``: value heads a key head of ``q, k`` serves, 1 but where the
    kernels of one decay a head read shared key heads in place."""
    from apex_tpu.amp.functional_patch import suspend
    with suspend():                     # float32 here whatever the policy
        with jax.named_scope("kda/scan"):
            if q.ndim == 3:             # (B, T, H d): the kernels' layout
                if g.shape == beta.shape:           # one decay a head
                    out, *kept = _gdn_forward_kernel(q, k, v, g, beta, group)
                else:
                    out, *kept = _forward_kernel(q, k, v, g, beta)
                return out, tuple(kept)
            qg, kg, w, ut, aqk, decay = _prepared(q, k, v, g, beta)
            states = _propagate(w, ut, kg, decay)
            out = _output(qg, aqk, w, ut, states)
    n, b, h, c, dv = out.shape
    out = jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(b, n * c, h, dv)
    return out, (states,)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, group):
    return _forward(q, k, v, g, beta, group)[0]


def _scan_fwd(q, k, v, g, beta, group):
    out, kept = _forward(q, k, v, g, beta, group)
    out, *kept = (checkpoint_name(x, KEPT_KDA) for x in (out, *kept))
    return out, (q, k, v, g, beta, *kept)


def _scan_bwd(group, res, d_out):
    from apex_tpu.amp.functional_patch import suspend
    q, k, v, g, beta, states, *inverse = res
    with suspend(), jax.named_scope("kda/scan"):
        if inverse and g.shape == beta.shape:
            return _gdn_backward_kernel(q, k, v, g, beta, states, *inverse,
                                        d_out, group)
        if inverse:
            return _backward_kernel(q, k, v, g, beta, states, *inverse, d_out)
        (qg, kg, w, ut, aqk, decay), back = jax.vjp(_prepared, q, k, v, g, beta)
        _, out_back = jax.vjp(_output, qg, aqk, w, ut, states)
        d_qg, d_aqk, d_w, d_ut, d_states = out_back(_chunked(d_out))
        p_w, p_ut, d_kg, d_decay = _propagate_bwd(w, ut, kg, decay, states,
                                                  d_states)
        return back((d_qg, d_kg, d_w + p_w, d_ut + p_ut, d_aqk, d_decay))


_scan.defvjp(_scan_fwd, _scan_bwd)


def gated_delta_rule(q, k, v, g, beta, head_dim=None):
    """``o_t = S_t^T q_t`` of the recurrence above, for every token.

    ``q, k``: ``(B, T, H_k, d_k)``; ``v``: ``(B, T, H, d_v)``; ``beta``:
    ``(B, T, H)``. ``g`` is the log of the decay (``<= 0``): ``(B, T, H,
    d_k)``, a value for each key channel (KDA), or ``(B, T, H)``, one value a
    head (gated DeltaNet: ``Diag(exp(g_t))`` a multiple of the identity).
    ``H_k`` divides ``H``: key head ``j`` serves the value heads ``j H / H_k
    ... (j + 1) H / H_k - 1``. The caller normalises and scales ``q`` and
    ``k``. Returns ``(B, T, H, d_v)`` in float32. Any length: a sequence is
    padded to whole chunks with tokens that leave the state as it is.

    Or the heads side by side, as a projection writes them and the kernels
    read them: ``q, k`` ``(B, T, H_k d_k)`` with ``head_dim = d_k`` stated,
    ``v`` ``(B, T, H d_v)``, ``g`` ``(B, T, H d_k)`` or ``(B, T, H)``. For
    head sizes the kernels take, operands in this layout reach them as they
    are and their cotangents come back in it (``(B, T, H, d)`` is another
    arrangement of the TPU's ``(8, 128)`` tiles: a copy each way). The
    output is ``(B, T, H, d_v)`` either way.

    What the op sees of the shapes picks the form, no argument does. For
    head sizes the kernels take, a decay a head takes kernels of its own
    (``apex_gdn_fwd``, ``apex_gdn_bwd``) that read ``g`` as it is, and key
    heads shared by exactly the value heads of a grid step
    (``HEADS_A_STEP``) where they are; other sharing reaches the kernels as
    what it is short for, ``q`` and ``k`` repeated a group, as does a decay
    a head the ``jax.numpy`` form, broadcast over the key channels. Exact,
    and the cotangents come back summed by the repeat's own transpose.
    """
    (b, t, h), flat = beta.shape, q.ndim == 3
    dk = head_dim if flat else q.shape[-1]
    dv = v.shape[-1] // h if flat else v.shape[-1]
    one_a_head = g.ndim == 3 and g.shape[-1] == h
    group = h * dk // (q.size // (b * t))    # value heads a key head
    if _tiled(dk, dv):
        # the kernels' layout, (B, T, H d). A head is a lane range there:
        # a repeat is written as ranges side by side, because (B, T, H,
        # group, d) is another arrangement of the tiles
        q, k, v, g = (x.reshape(b, t, -1) for x in (q, k, v, g))
        if group > 1 and not (one_a_head and group == _step_heads(h)):
            q, k = (jnp.concatenate(
                [x[..., i:i + dk] for i in range(0, x.shape[-1], dk)
                 for _ in range(group)], -1) for x in (q, k))
            group = 1
    else:
        q, k = (jnp.broadcast_to(x.reshape(b, t, -1, 1, dk),
                                 (b, t, h // group, group, dk))
                .reshape(b, t, h, dk) for x in (q, k))
        v = v.reshape(b, t, h, dv)
        g = (jnp.broadcast_to(g[..., None], (b, t, h, dk)) if one_a_head
             else g.reshape(b, t, h, dk))
        group = 1
    pad = -t % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    if (h > HEAD_GROUP and h % HEAD_GROUP == 0 and q.ndim == 4):
        grouped = lambda x: jnp.moveaxis(x.reshape(
            *x.shape[:2], h // HEAD_GROUP, HEAD_GROUP, *x.shape[3:]), 2, 0)
        out = lax.map(lambda xs: _scan(*xs, 1),
                      tuple(map(grouped, (q, k, v, g, beta))))
        out = jnp.moveaxis(out, 0, 2).reshape(*q.shape[:3], -1)
    else:
        out = _scan(q, k, v, g, beta, group)
    return out[:, :t]


def gated_delta_rule_reference(q, k, v, g, beta):
    """The recurrence as written, one ``lax.scan`` step a token: the oracle
    of the tests. ``g`` a key channel or a head, ``q`` and ``k`` with the
    value heads or with a divisor of them, as the op takes them."""
    f32 = lambda x: jnp.swapaxes(x.astype(jnp.float32), 0, 1)
    b, _, h, dv = v.shape
    dk = q.shape[-1]
    # value head i reads key head i // group
    key_head = jnp.arange(h) // (h // q.shape[2])

    def step(state, xs):
        q, k, v, g, beta = xs
        q, k = q[:, key_head], k[:, key_head]
        state = state * jnp.exp(g).reshape(b, h, -1, 1)
        u = (v - jnp.einsum("bhkv,bhk->bhv", state, k)) * beta[..., None]
        state = state + k[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    zero = jnp.zeros((b, h, dk, dv), jnp.float32)
    out = lax.scan(step, zero, tuple(map(f32, (q, k, v, g, beta))))[1]
    return jnp.swapaxes(out, 0, 1)
