"""A matmul over rows sorted by group, each group against its own matrix.

``lhs`` is ``(M, K)`` with the rows of group 0 first, then group 1's, and so
on; ``sizes (G,)`` int32 says how many each group has, read on the device.
The rows past ``sum(sizes)`` belong to no group: they are not read, and what
comes back in their place means nothing (zeros inside a tile that was
visited, whatever the memory held elsewhere).

:func:`grouped_matmul`     ``out[rows of g] = sum_p lhs_p[rows of g] @ rhs_p[g]``
                           (``rhs_p`` ``(G, K, N)``, or ``(G, N, K)`` with
                           ``transposed``: the form a backward needs);
:func:`grouped_matmul_t`   ``out[g] = lhs[rows of g]^T @ rhs[rows of g]``,
                           ``(G, K, N)``, zeros for a group with no row
                           (``lhs`` may come in parts that are summed).
                           ``rhs`` is finite wherever a tile holds a row
                           of a group: its other rows meet zeros.

Two Pallas kernels, ``apex_gmm`` and ``apex_tgmm``, in the shape of
``jax.experimental.pallas.ops.tpu.megablox``: the rows are cut into tiles of
:data:`ROW_TILE`, and the grid's row axis walks *visits*, a (group, tile)
pair for each tile a group has a row in, in order. How many visits there
are is the grid's bound, a number read on the device, so the cost follows
``sum(sizes)`` and not ``M``. The group and the tile of each visit reach the
index maps by scalar prefetch; a tile that holds the end of one group and
the start of the next is visited once for each, and a 0/1 mask over its rows
keeps each visit to its own (:func:`visits` is the arithmetic, also what a
counter reads). Products accumulate in float32 and leave in ``lhs``'s dtype.

Tiles. The contraction is never cut in ``apex_gmm`` (one ``dot`` a visit, no
accumulator to carry); the columns are, into the widest multiple of 128
that divides them and fits :data:`VMEM_BUDGET` with every block double
buffered (:func:`_gmm_columns`; docs/layers.md has the reckoning).
``apex_tgmm`` keeps one ``(tk, tn)`` float32 accumulator for the group it is
in and cuts both of the result's axes (:func:`_tgmm_tiles`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.ops._dispatch import jit_launcher, pallas_call, use_interpret

#: rows of a tile, at most (a shorter array is one tile of whole sublanes)
ROW_TILE = 512
#: bytes of VMEM a kernel's blocks may take by the reckoning below, and the
#: scoped limit asked of Mosaic (its default is 16 MiB of a v5e's 128)
VMEM_BUDGET = 28 * 2 ** 20
VMEM_LIMIT = 40 * 2 ** 20


def row_tile(m):
    """Rows of a tile for ``m`` rows: :data:`ROW_TILE`, or all of fewer in
    whole packed sublanes. The caller makes ``m`` a multiple of it."""
    return min(ROW_TILE, -(-m // 16) * 16)


def visits(sizes, m, tile, empty=False):
    """The grid's row axis. ``sizes (..., G)``: rows of each group, in order
    from row 0; ``m`` rows in tiles of ``tile``. Returns ``(offsets (..., G +
    1), group (..., S), at (..., S), n (...,))``: visit ``i < n`` is of group
    ``group[i]`` at tile ``at[i]``, and there are ``S = m / tile + G - 1``
    places for them. A group visits every tile it has a row in; with
    ``empty`` a group of no rows visits one tile all the same (its result is
    written there, as zeros)."""
    sizes = jnp.asarray(sizes, jnp.int32)
    g, tiles = sizes.shape[-1], m // tile
    ends = jnp.cumsum(sizes, -1)
    first = (ends - sizes) // tile
    count = jnp.where(sizes > 0, -(-ends // tile) - first, int(empty))
    upto = jnp.cumsum(count, -1)
    step = jnp.arange(tiles + g - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(step[:, None] >= upto[..., None, :], -1,
                                dtype=jnp.int32), g - 1)
    take = lambda a: jnp.take_along_axis(a, group, -1)
    at = jnp.minimum(take(first) + step - take(upto - count), tiles - 1)
    offsets = jnp.concatenate([jnp.zeros_like(ends[..., :1]), ends], -1)
    return offsets, group, at, upto[..., -1]


def _mine(offsets, group, at, i, tile):
    """``(tile, 1)`` bool: the rows of visit ``i``'s tile that are its
    group's."""
    g = group[i]
    rows = at[i] * tile + jax.lax.broadcasted_iota(jnp.int32, (tile, 1), 0)
    return (rows >= offsets[g]) & (rows < offsets[g + 1])


def _dot(a, b, dims):
    """Float32 sums of ``a`` and ``b``'s products. Half operands name their
    precision: under a ``jax.default_matmul_precision`` of ``highest`` a
    bfloat16 product would ask Mosaic for float32 passes, which it refuses;
    float32 operands take the ambient one."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=None if a.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT)


def _masked(ref, mine):
    # a select on 32-bit lanes: the mask is one
    return jnp.where(mine, ref[...].astype(jnp.float32), 0.0).astype(ref.dtype)


def _gmm_kernel(pairs, tile, transposed, offsets, group, at, *refs):
    from jax.experimental import pallas as pl
    out = refs[-1]
    i = pl.program_id(1)
    dims = ((1,), (1 if transposed else 0,))
    acc = sum(_dot(refs[p][...], refs[pairs + p][...], dims)
              for p in range(pairs))
    mine = _mine(offsets, group, at, i, tile)
    # a tile's first visit writes all of it; a later one leaves the others'
    first = (i == 0) | (at[i] != at[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _():
        out[...] = jnp.where(mine, acc, 0.0).astype(out.dtype)

    @pl.when(~first)
    def _():
        out[...] = jnp.where(mine, acc, out[...].astype(jnp.float32)).astype(
            out.dtype)


def _tgmm_kernel(tile, offsets, group, at, *refs):
    from jax.experimental import pallas as pl
    *lhs, rhs, out, acc = refs
    i, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group[i]

    @pl.when((i == 0) | (group[jnp.maximum(i - 1, 0)] != g))
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(offsets[g + 1] > offsets[g])
    def _():
        # the others' rows of the tile meet zeros: masked on one side, which
        # is enough while what a visited tile holds is finite
        mine = _mine(offsets, group, at, i, tile)
        acc[...] += sum(_dot(_masked(part, mine), rhs[...], ((0,), (0,)))
                        for part in lhs)

    @pl.when((i == last) | (group[jnp.minimum(i + 1, last)] != g))
    def _():
        out[...] = acc[...].astype(out.dtype)


def _divisors(n):
    """The column tiles ``n`` allows, widest first: multiples of 128 that
    divide it, or all of it."""
    return [w for w in range(n, 0, -128) if n % w == 0 and w % 128 == 0] or [n]


def _gmm_columns(tile, k, n, pairs, itemsize):
    """Columns of an ``apex_gmm`` block: ``pairs`` lhs ``(tile, k)`` and rhs
    ``(k, tn)`` blocks and the ``(tile, tn)`` result twice over, and its
    float32 product beside the masked copy; float32 operands twice more (the matrix unit
    takes them as bfloat16 parts, which Mosaic keeps beside them)."""
    parts = 4 if itemsize == 4 else 2
    fits = lambda tn: (parts * pairs * (tile * k + k * tn) * itemsize
                       + 2 * tile * tn * itemsize + 2 * tile * tn * 4
                       <= VMEM_BUDGET)
    return next((tn for tn in _divisors(n) if fits(tn)), _divisors(n)[-1])


def _tgmm_tiles(tile, k, n, itemsize, lhs=1, out_itemsize=None):
    """``(tk, tn)`` of an ``apex_tgmm`` block: the operands' blocks (``lhs``
    of the left one) and the result's twice over, the left ones' masked
    copies (float32 for the select, then as they came), the float32
    accumulator and a product beside it; of what fits, the pair that reads
    the operands least often."""
    parts = 4 if itemsize == 4 else 2
    fits = lambda tk, tn: (parts * tile * (lhs * tk + tn) * itemsize
                           + tile * lhs * tk * (4 + itemsize)
                           + 2 * tk * tn * (out_itemsize or itemsize)
                           + 2 * tk * tn * 4 <= VMEM_BUDGET)
    reads = lambda tk, tn: k * (n // tn) + n * (k // tk)
    options = [(tk, tn) for tk in _divisors(k) for tn in _divisors(n)
               if fits(tk, tn)] or [(_divisors(k)[-1], _divisors(n)[-1])]
    return min(options, key=lambda t: (reads(*t), -t[0] * t[1]))


def _params(semantics):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=None if use_interpret() else VMEM_LIMIT)


# The launchers are jitted (a decoder's step holds a dozen calls a layer, of
# six shapes); the rows' tile is an argument, so that it keys the trace.

@jit_launcher(static_argnums=(3, 4))
def _gmm(lhs, rhs, sizes, transposed, tile):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (m, k), pairs = lhs[0].shape, len(lhs)
    n = rhs[0].shape[1 if transposed else 2]
    tn = _gmm_columns(tile, k, n, pairs, lhs[0].dtype.itemsize)
    *plan, steps = visits(sizes, m, tile)
    rows = pl.BlockSpec((tile, k), lambda j, i, offsets, group, at:
                        (at[i], 0))
    weights = pl.BlockSpec(
        (None, tn, k) if transposed else (None, k, tn),
        lambda j, i, offsets, group, at:
        (group[i], j, 0) if transposed else (group[i], 0, j))
    return pallas_call(
        functools.partial(_gmm_kernel, pairs, tile, transposed),
        name="apex_gmm",
        out_shape=jax.ShapeDtypeStruct((m, n), lhs[0].dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, steps),
            in_specs=[rows] * pairs + [weights] * pairs,
            out_specs=pl.BlockSpec((tile, tn), lambda j, i, offsets, group,
                                   at: (at[i], j))),
        compiler_params=_params(("parallel", "arbitrary")))(
            *plan, *lhs, *rhs)


@jit_launcher(static_argnums=(3, 4))
def _tgmm(lhs, rhs, sizes, dtype, tile):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (m, k), n = lhs[0].shape, rhs.shape[1]
    tk, tn = _tgmm_tiles(tile, k, n, rhs.dtype.itemsize, len(lhs),
                         jnp.dtype(dtype).itemsize)
    *plan, steps = visits(sizes, m, tile, empty=True)
    left = pl.BlockSpec((tile, tk), lambda j, c, i, offsets, group, at:
                        (at[i], c))
    return pallas_call(
        functools.partial(_tgmm_kernel, tile), name="apex_tgmm",
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, k // tk, steps),
            in_specs=[left] * len(lhs) + [
                pl.BlockSpec((tile, tn), lambda j, c, i, offsets, group, at:
                             (at[i], j))],
            out_specs=pl.BlockSpec((None, tk, tn), lambda j, c, i, offsets,
                                   group, at: (group[i], c, j)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_params(("parallel", "arbitrary", "arbitrary")))(
            *plan, *lhs, rhs)


@jit_launcher(static_argnums=(0, 1))
def _unwritten(shape, dtype):
    from jax.experimental import pallas as pl
    return pallas_call(
        lambda out: None, name="apex_unwritten",
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY))()


def unwritten(shape, dtype):
    """An array of ``shape`` that nothing has written: what it holds means
    nothing, as the rows past the groups in a kernel's result do. For a
    buffer whose live rows a loop fills (``ops/moe.py``), where a fill with
    zeros would cost a pass over all of it, whatever is live."""
    return _unwritten(tuple(shape), jnp.dtype(dtype))


def _whole_tiles(rows):
    tile = row_tile(rows)
    if rows % tile:
        raise ValueError(f"{rows} rows are no whole tiles of {tile}")
    return tile


def grouped_matmul(lhs, rhs, sizes, transposed=False):
    """``out[rows of g] = sum over the pairs of lhs[rows of g] @ rhs[g]``
    (``@ rhs[g]^T`` with ``transposed``). ``lhs`` ``(M, K)`` and ``rhs`` ``(G,
    K, N)`` (``(G, N, K)`` transposed), or a tuple of each, pair by pair of
    one shape and dtype; ``sizes (G,)`` int32. ``M`` is a multiple of
    :func:`row_tile`. Returns ``(M, N)`` in ``lhs``'s dtype."""
    lhs, rhs = ((lhs,), (rhs,)) if not isinstance(lhs, tuple) else (lhs, rhs)
    return _gmm(tuple(lhs), tuple(rhs), sizes.astype(jnp.int32), transposed,
                _whole_tiles(lhs[0].shape[0]))


def grouped_matmul_t(lhs, rhs, sizes, dtype=None):
    """``out[g] = sum over lhs of lhs[rows of g]^T @ rhs[rows of g]``:
    ``lhs`` ``(M, K)`` or a tuple of such (a number in parts: their products
    are summed in float32 before anything is rounded), ``rhs`` ``(M, N)``,
    ``sizes (G,)`` int32. Returns ``(G, K, N)`` in ``dtype`` (``rhs``'s), a
    group of no rows all zeros."""
    lhs = lhs if isinstance(lhs, tuple) else (lhs,)
    return _tgmm(lhs, rhs, sizes.astype(jnp.int32),
                 jnp.dtype(dtype or rhs.dtype), _whole_tiles(rhs.shape[0]))
