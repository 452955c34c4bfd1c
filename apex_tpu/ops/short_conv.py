"""The short convolution of a token mixer, with what goes round it.

``y = SiLU(causal depthwise convolution of x over the K newest tokens)``,
and on the channel ranges the caller names each head of ``y`` divided by its
l2-norm (``y rsqrt(sum y^2 + 1e-6)``) and times a constant: what Kimi Delta
Attention and gated DeltaNet do to a projection before the scan reads it
(``q`` normalised and scaled, ``k`` normalised, ``v`` plain).

The caller may instead name two more lane ranges of the same array as
gates: ``y = g_after * conv(g_before * x)``, with no activation, the whole of
a gated short-convolution mixer between its two projections (``[x; g_after;
g_before]`` side by side in one projection's output). That form leaves in
``x``'s dtype, for the projection that reads it, and its backward writes the
three cotangents as one array of ``x``'s shape: no concatenation follows.

Layout. In and out are ``(B, T, C)``, a head a run of ``head_dim`` channels:
the layout the projection's GEMM writes and the one ``apex_kda_fwd`` reads
(``ops/delta_rule.py``), so nothing is moved between the three. A head of
whole 128-lane tiles is a lane range of a block, its sum of squares a lane
reduction: no ``(B, T, H, d)`` array exists.

Head sizes of whole 128-lane tiles (``ops/delta_rule.py`` ``_tiled``: the
published sizes) take two Pallas kernels, ``apex_short_conv_fwd`` and
``apex_short_conv_bwd``, over blocks of ``(tokens, channels)``. The ``K - 1``
tokens before a block come from a second block of the same array (the
``HALO`` rows before it, zero at the sequence's start); the backward reads
the rows after it the same way, of ``x`` and of the cotangent. The backward
computes the convolution again from ``x``, so the op keeps ``x`` and the taps
and nothing else, writes ``d x`` in ``x``'s dtype and sums ``d taps`` over
batch and tokens in a block that stays in VMEM. Any other head size takes
the ``jax.numpy`` form, :func:`short_conv_reference`. The head size picks
the path; no argument does. The gated form has kernel bodies of its own
under the same two names: one more grid axis in the backward walks the
ranges of ``d x``, whose three parts a step computes at once and keeps in
VMEM until their turn.

Precision. ``x`` is read as it comes (bfloat16 under O1) and upcast in VMEM;
convolution, SiLU, norm, gates and every cotangent are float32
(``short_conv`` is a FLOAT op of ``amp/lists.py``) and the output is float32
(``x``'s dtype with gates), on both paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from apex_tpu.ops._dispatch import jit_launcher, pallas_call
from apex_tpu.ops.delta_rule import _tiled

EPS = 1e-6
#: rows of the neighbouring block a kernel reads: one bfloat16 tile
HALO = 16
#: tokens and channels of a block, at most: the fastest of six shapes on a
#: v5e at the decoder cells' sizes, forward and backward (PERF.md, PR 33)
BLOCK_T = 256
BLOCK_C = 1024


def _conv(x, taps):
    """Causal depthwise convolution over the ``len(taps)`` newest tokens.
    ``x`` ``(B, T, C)``, ``taps`` ``(K, C)``, newest last."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t] * taps[j] for j in range(k))


def _short_conv(x, taps):
    """:func:`_conv`, then SiLU."""
    return jax.nn.silu(_conv(x, taps))


def _l2_normalised(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + EPS)


def short_conv_reference(x, taps, norm=(), head_dim=128, gates=None):
    """:func:`short_conv` as ``jax.numpy``: the form of head sizes that are
    no whole lane tiles, and the oracle of the tests."""
    c = taps.shape[1]
    if gates is not None:
        assert not norm
        before, after = (x[..., at:at + c].astype(jnp.float32)
                         for at in gates)
        y = after * _conv(x[..., :c].astype(jnp.float32) * before, taps)
        return y.astype(x.dtype)
    y = _short_conv(x[..., :c], taps)
    parts, at = [], 0
    for lo, hi, scale in norm:
        heads = y[..., lo:hi].reshape(*y.shape[:2], -1, head_dim)
        parts += [y[..., at:lo],
                  (_l2_normalised(heads) * scale).reshape(*y.shape[:2], -1)]
        at = hi
    return jnp.concatenate(parts + [y[..., at:]], -1)


# ---- the Pallas kernels: a block of tokens and a few heads a grid step ------
#
# The grid is ``(channel blocks, batch, token blocks)``: the backward's
# ``d taps`` block depends on the first alone and stays in VMEM while the
# other two run. A block's rows and its neighbours' go into one float32
# scratch, from which each tap reads the block shifted by its distance.

def _head_scale(norm, channel):
    """The constant of the head that starts at ``channel`` (traced), 0 where
    the head is not normalised."""
    scale = 0.0
    for lo, hi, s in norm:
        scale = jnp.where((channel >= lo) & (channel < hi), s, scale)
    return scale


def _heads(norm, head, width):
    """``(columns, scale)`` of each head of a block ``width`` channels wide."""
    from jax.experimental import pallas as pl
    first = pl.program_id(0) * width
    return [(slice(j, j + head), _head_scale(norm, first + j))
            for j in range(0, width, head)]


def _preactivation(ext, taps_ref, cols, rows):
    """The convolution on ``rows`` rows from ``HALO`` on of the scratch, and
    the shifted rows each tap read."""
    k = taps_ref.shape[0]
    shifted = [ext[HALO - (k - 1) + i:HALO - (k - 1) + i + rows, cols]
               for i in range(k)]
    return sum(x * taps_ref[i:i + 1, cols]
               for i, x in enumerate(shifted)), shifted


def _fwd_kernel(norm, head, x_ref, before_ref, taps_ref, out_ref, ext):
    from jax.experimental import pallas as pl
    rows = x_ref.shape[0]
    ext[:HALO] = jnp.where(pl.program_id(2) == 0, 0.0,
                           before_ref[...].astype(jnp.float32))
    ext[HALO:] = x_ref[...].astype(jnp.float32)
    for cols, scale in _heads(norm, head, x_ref.shape[1]):
        pre, _ = _preactivation(ext, taps_ref, cols, rows)
        y = pre * jax.nn.sigmoid(pre)
        if not norm:
            out_ref[:, cols] = y
            continue

        @pl.when(scale != 0)
        def _():
            out_ref[:, cols] = y * (scale * jax.lax.rsqrt(
                jnp.sum(y * y, -1, keepdims=True) + EPS))

        @pl.when(scale == 0)
        def _():
            out_ref[:, cols] = y


def _bwd_kernel(norm, head, x_ref, before_ref, after_ref, taps_ref, dy_ref,
                dy_after_ref, dx_ref, dtaps_ref, ext, dpre):
    from jax.experimental import pallas as pl
    k, rows = taps_ref.shape[0], x_ref.shape[0]
    step, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    ext[:HALO] = jnp.where(step == 0, 0.0,
                           before_ref[...].astype(jnp.float32))
    ext[HALO:HALO + rows] = x_ref[...].astype(jnp.float32)
    # after the sequence's end the cotangent is zero: these rows only have
    # to be finite there (the index map hands the last block's own)
    ext[HALO + rows:] = after_ref[...].astype(jnp.float32)
    dpre[:rows] = dy_ref[...]
    dpre[rows:] = jnp.where(step == last, 0.0, dy_after_ref[...])
    for cols, scale in _heads(norm, head, x_ref.shape[1]):
        # d pre on the block's rows and on the K - 1 after them that read it
        pre, shifted = _preactivation(ext, taps_ref, cols, rows + HALO)
        sig = jax.nn.sigmoid(pre)
        if norm:
            @pl.when(scale != 0)
            def _():
                y, dy = pre * sig, dpre[:, cols]
                r = jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + EPS)
                dpre[:, cols] = scale * r * (
                    dy - y * (r * r * jnp.sum(dy * y, -1, keepdims=True)))
        d = dpre[:, cols] * (sig * (1.0 + pre * (1.0 - sig)))
        dpre[:, cols] = d
        for i, x in enumerate(shifted):
            dtaps_ref[i:i + 1, cols] += jnp.sum(d[:rows] * x[:rows], 0,
                                                keepdims=True)
        dx_ref[:, cols] = sum(
            dpre[k - 1 - i:k - 1 - i + rows, cols] * taps_ref[i:i + 1, cols]
            for i in range(k)).astype(dx_ref.dtype)


# The gated form, ``y = g_after * conv(g_before * x)``. ``x`` and both gates
# are blocks of one array at three channel offsets. The backward's grid has a
# fourth axis over the ``C``-wide parts of ``d x``: its first turn computes
# the three cotangents of a block and writes ``x``'s, the gates' wait in VMEM
# for the turn of their part (any other part is zero).

def _columns(width, head):
    return [slice(j, j + head) for j in range(0, width, head)]


def _gated_fwd_kernel(head, x_ref, x_before_ref, g_ref, g_before_ref,
                      gate_ref, taps_ref, out_ref, ext):
    from jax.experimental import pallas as pl
    rows, f32 = x_ref.shape[0], jnp.float32
    ext[:HALO] = jnp.where(
        pl.program_id(2) == 0, 0.0,
        x_before_ref[...].astype(f32) * g_before_ref[...].astype(f32))
    ext[HALO:] = x_ref[...].astype(f32) * g_ref[...].astype(f32)
    for cols in _columns(x_ref.shape[1], head):
        pre, _ = _preactivation(ext, taps_ref, cols, rows)
        out_ref[:, cols] = (pre * gate_ref[:, cols].astype(f32)).astype(
            out_ref.dtype)


def _gated_bwd_kernel(head, parts, x_ref, x_before_ref, g_ref, g_before_ref,
                      gate_ref, gate_after_ref, taps_ref, dy_ref,
                      dy_after_ref, dx_ref, dtaps_ref, ext, dconv, d_g, d_gate):
    from jax.experimental import pallas as pl
    k, rows, f32 = taps_ref.shape[0], x_ref.shape[0], jnp.float32
    step, last = pl.program_id(2), pl.num_programs(2) - 1
    part = pl.program_id(3)

    @pl.when((pl.program_id(1) == 0) & (step == 0) & (part == 0))
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    @pl.when(part == 0)
    def _():
        ext[:HALO] = jnp.where(
            step == 0, 0.0,
            x_before_ref[...].astype(f32) * g_before_ref[...].astype(f32))
        ext[HALO:] = x_ref[...].astype(f32) * g_ref[...].astype(f32)
        # d conv on the block's rows and on the K - 1 after them
        dconv[:rows] = dy_ref[...].astype(f32) * gate_ref[...].astype(f32)
        dconv[rows:] = jnp.where(
            step == last, 0.0,
            dy_after_ref[...].astype(f32) * gate_after_ref[...].astype(f32))
        for cols in _columns(x_ref.shape[1], head):
            conv, shifted = _preactivation(ext, taps_ref, cols, rows)
            d_gate[:, cols] = (dy_ref[:, cols].astype(f32) * conv).astype(
                d_gate.dtype)
            d = dconv[:rows, cols]
            for i, x in enumerate(shifted):
                dtaps_ref[i:i + 1, cols] += jnp.sum(d * x, 0, keepdims=True)
            d_in = sum(
                dconv[k - 1 - i:k - 1 - i + rows, cols]
                * taps_ref[i:i + 1, cols] for i in range(k))
            dx_ref[:, cols] = (d_in * g_ref[:, cols].astype(f32)).astype(
                dx_ref.dtype)
            d_g[:, cols] = (d_in * x_ref[:, cols].astype(f32)).astype(
                d_g.dtype)

    for at, kept in zip(parts, (d_g, d_gate)):
        @pl.when(part == at)
        def _():
            dx_ref[...] = kept[...]

    @pl.when((part != 0) & (part != parts[0]) & (part != parts[1]))
    def _():
        dx_ref[...] = jnp.zeros_like(dx_ref)


def _specs(x, taps, head):
    """Grid and block specs of both kernels for ``x`` ``(B, T, C)`` of whole
    blocks: a block of tokens and whole heads, the ``HALO`` rows before it
    and after it (clamped to the array), a block of the taps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    (b, t, _), c, rows = x.shape, taps.shape[1], _rows(x.shape[1])
    # whole heads, as many as BLOCK_C holds and C is a multiple of
    width = max(w for w in range(head, max(BLOCK_C, head) + 1, head)
                if c % w == 0)
    halos, ratio = t // HALO, rows // HALO
    halo = lambda at: pl.BlockSpec((None, HALO, width),
                                   lambda c, b, n: (b, at(n), c))
    return dict(
        grid=(c // width, b, t // rows), rows=rows,
        block=pl.BlockSpec((None, rows, width), lambda c, b, n: (b, n, c)),
        before=halo(lambda n: jnp.maximum(n * ratio - 1, 0)),
        after=halo(lambda n: jnp.minimum((n + 1) * ratio, halos - 1)),
        taps=pl.BlockSpec((taps.shape[0], width), lambda c, b, n: (0, c)),
        scratch=lambda r: pltpu.VMEM((r, width), jnp.float32),
        params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")))


def _rows(t):
    """Tokens of a block: whole ``HALO``s."""
    return min(BLOCK_T, -(-t // HALO) * HALO)


def _whole_blocks(x):
    """``x`` with its token axis padded with zeros to whole blocks."""
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % _rows(x.shape[1])), (0, 0)))


def _fwd_call(kernel, **kwargs):
    return pallas_call(kernel, name="apex_short_conv_fwd", **kwargs)


def _bwd_call(kernel, **kwargs):
    return pallas_call(kernel, name="apex_short_conv_bwd", **kwargs)


# The launchers are jitted: a decoder's step holds dozens of these calls
# (three a KDA layer, again in a block's rerun), of three kinds.

@jit_launcher(static_argnums=(2, 3))
def _forward(x, taps, norm, head):
    t, x = x.shape[1], _whole_blocks(x)
    sp = _specs(x, taps, head)
    return _fwd_call(
        functools.partial(_fwd_kernel, norm, head), grid=sp["grid"],
        in_specs=[sp["block"], sp["before"], sp["taps"]],
        out_specs=sp["block"],
        out_shape=jax.ShapeDtypeStruct((*x.shape[:2], taps.shape[1]),
                                       jnp.float32),
        scratch_shapes=[sp["scratch"](HALO + sp["rows"])],
        compiler_params=sp["params"])(x, x, taps)[:, :t]


@jit_launcher(static_argnums=(3, 4))
def _backward(x, taps, d_out, norm, head):
    t, channels = d_out.shape[1], x.shape[2]
    x, d_out = map(_whole_blocks, (x, d_out.astype(jnp.float32)))
    sp = _specs(x, taps, head)
    d_x, d_taps = _bwd_call(
        functools.partial(_bwd_kernel, norm, head), grid=sp["grid"],
        in_specs=[sp["block"], sp["before"], sp["after"], sp["taps"],
                  sp["block"], sp["after"]],
        out_specs=[sp["block"], sp["taps"]],
        out_shape=[jax.ShapeDtypeStruct(d_out.shape, x.dtype),
                   jax.ShapeDtypeStruct(taps.shape, jnp.float32)],
        scratch_shapes=[sp["scratch"](HALO + sp["rows"] + HALO),
                        sp["scratch"](sp["rows"] + HALO)],
        compiler_params=sp["params"])(x, x, x, taps, d_out, d_out)
    # the channels of x past the taps' were not read
    return jnp.pad(d_x[:, :t], ((0, 0), (0, 0),
                                (0, channels - d_x.shape[2]))), d_taps


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _kernels(x, taps, norm, head):
    return _forward(x, taps, norm, head)


def _kernels_fwd(x, taps, norm, head):
    return _forward(x, taps, norm, head), (x, taps)


def _kernels_bwd(norm, head, res, d_out):
    from apex_tpu.amp.functional_patch import suspend
    with suspend():
        return _backward(*res, d_out, norm, head)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def _shifted(spec, blocks):
    """``spec`` with its channel block index ``blocks`` further on."""
    from jax.experimental import pallas as pl

    def index_map(c, b, n, *part):
        *lead, channel = spec.index_map(c, b, n)
        return (*lead, channel + blocks)
    return pl.BlockSpec(spec.block_shape, index_map)


@jit_launcher(static_argnums=(2, 3))
def _gated_forward(x, taps, gates, head):
    t, x = x.shape[1], _whole_blocks(x)
    sp = _specs(x, taps, head)
    g, gate = (sp["grid"][0] * part for part in gates)
    return _fwd_call(
        functools.partial(_gated_fwd_kernel, head), grid=sp["grid"],
        in_specs=[sp["block"], sp["before"], _shifted(sp["block"], g),
                  _shifted(sp["before"], g), _shifted(sp["block"], gate),
                  sp["taps"]],
        out_specs=sp["block"],
        out_shape=jax.ShapeDtypeStruct((*x.shape[:2], taps.shape[1]),
                                       x.dtype),
        scratch_shapes=[sp["scratch"](HALO + sp["rows"])],
        compiler_params=sp["params"])(x, x, x, x, x, taps)[:, :t]


@jit_launcher(static_argnums=(3, 4))
def _gated_backward(x, taps, d_out, gates, head):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    t = d_out.shape[1]
    x, d_out = map(_whole_blocks, (x, d_out))
    sp = _specs(x, taps, head)
    rows, width = sp["block"].block_shape[1:]
    across = sp["grid"][0]                  # channel blocks of a part
    g, gate = (across * part for part in gates)
    every = lambda spec: _shifted(spec, 0)  # the grid's fourth index dropped
    kept = pltpu.VMEM((rows, width), x.dtype)
    d_x, d_taps = _bwd_call(
        functools.partial(_gated_bwd_kernel, head, gates),
        grid=(*sp["grid"], x.shape[2] // taps.shape[1]),
        in_specs=[every(sp["block"]), every(sp["before"]),
                  _shifted(sp["block"], g), _shifted(sp["before"], g),
                  _shifted(sp["block"], gate), _shifted(sp["after"], gate),
                  every(sp["taps"]), every(sp["block"]), every(sp["after"])],
        out_specs=[pl.BlockSpec(sp["block"].block_shape,
                                lambda c, b, n, part: (b, n, part * across + c)),
                   every(sp["taps"])],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(taps.shape, jnp.float32)],
        scratch_shapes=[sp["scratch"](HALO + rows), sp["scratch"](rows + HALO),
                        kept, kept],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "arbitrary", "arbitrary", "arbitrary")))(
                x, x, x, x, x, x, taps, d_out, d_out)
    return d_x[:, :t], d_taps


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated_kernels(x, taps, gates, head):
    return _gated_forward(x, taps, gates, head)


def _gated_kernels_fwd(x, taps, gates, head):
    return _gated_forward(x, taps, gates, head), (x, taps)


def _gated_kernels_bwd(gates, head, res, d_out):
    from apex_tpu.amp.functional_patch import suspend
    with suspend():
        return _gated_backward(*res, d_out, gates, head)


_gated_kernels.defvjp(_gated_kernels_fwd, _gated_kernels_bwd)


def short_conv(x, taps, norm=(), head_dim=128, gates=None):
    """``SiLU(conv(x))`` with the heads of the ranges ``norm`` l2-normalised,
    or ``g_after * conv(g_before * x)`` with both gates read from ``x``.

    ``x``: ``(B, T, C)``, any float dtype; ``taps``: ``(K, C)`` float32,
    the newest token's last: ``conv(x)_t = sum_j taps_j x_(t - K + 1 + j)``
    with ``x`` zero before the sequence. ``x`` may have channels past the
    taps' ``C`` (a projection that holds more than the convolution's
    inputs): they are not read, and the kernels take the first ``C`` from
    the array as it is, where a slice in front of them would be a copy. ``norm``: ``(start, stop, scale)``
    channel ranges, in order and apart, of whole heads of ``head_dim``
    channels: each head there leaves as ``scale y rsqrt(sum y^2 + 1e-6)``,
    every other channel as ``y``. ``gates``: ``(before, after)``, the
    channels of ``x`` at which two more ``C``-wide ranges start (whole
    multiples of ``C``, and ``x`` whole ``C``s wide): the first multiplies
    ``x`` before the taps and the second the result; that form has no
    activation and takes no ``norm``. Returns ``(B, T, C)`` float32, with ``gates`` in ``x``'s
    dtype; the gradients come back in ``x``'s dtype (with ``gates`` the
    three ranges' in one array, the rest of it zero) and float32 for the
    taps.
    """
    from apex_tpu.amp.functional_patch import suspend
    norm = tuple((int(lo), int(hi), float(s)) for lo, hi, s in norm)
    assert taps.shape[1] % head_dim == 0 and all(
        lo % head_dim == 0 and hi % head_dim == 0 and s != 0
        for lo, hi, s in norm), (x.shape, norm, head_dim)
    kernels = _tiled(head_dim, head_dim) and taps.shape[0] <= HALO
    with suspend():                     # float32 here whatever the policy
        taps = taps.astype(jnp.float32)
        if gates is not None:
            c = taps.shape[1]
            parts = tuple(int(at) // c for at in gates)
            assert (not norm and x.shape[2] % c == 0
                    and all(at % c == 0 for at in gates)
                    and len({0, *parts}) == 3), (x.shape, c, gates)
            if kernels:
                return _gated_kernels(x, taps, parts, head_dim)
        elif kernels:
            return _kernels(x, taps, norm, head_dim)
        return short_conv_reference(x, taps, norm, head_dim, gates)
