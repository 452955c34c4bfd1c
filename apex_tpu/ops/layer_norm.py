"""Fused LayerNorm — Pallas forward/backward with custom VJP.

TPU-native rebuild of `fused_layer_norm_cuda`
(`csrc/layer_norm_cuda.cpp:1-241`, `layer_norm_cuda_kernel.cu:280-807`):
one kernel normalizes a block of rows (statistics + normalize + affine in a
single VMEM pass, `cuApplyLayerNorm`), and the backward kernel produces
dgrad plus *partial* weight/bias gradient blocks that are reduced in a
second stage (`cuComputePartGradGammaBeta` → `cuComputeGradInput`).

Design delta: the reference saves (mean, invvar) as residuals; here the
backward kernel *recomputes* them from the saved input — on TPU the row
reduction is free next to the mandatory HBM re-read of ``x``, and dropping
the residual saves memory and a layout-awkward (N,) tensor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_call

LANES = 128


def _row_block(h_padded: int, n_bufs: int) -> int:
    """Rows per grid step: keep n_bufs (R, Hp) fp32 buffers ≤ ~1 MiB each
    so double buffering stays well inside VMEM; multiple of 16 to satisfy
    the widest (bf16) tiling."""
    r = (1 << 20) // (4 * h_padded)
    r = max(16, min(256, (r // 16) * 16))
    return r


def _pad2(x2, rows, h_padded):
    n, h = x2.shape
    if n == rows and h == h_padded:
        return x2
    return jnp.pad(x2, ((0, rows - n), (0, h_padded - h)))


def _col_mask(h, h_padded, rows):
    cols = jax.lax.broadcasted_iota(jnp.int32, (rows, h_padded), 1)
    return cols < h


def _moments(x, h, mask):
    xm = jnp.where(mask, x, 0.0)
    mean = jnp.sum(xm, axis=1, keepdims=True) / h
    var = jnp.sum(jnp.where(mask, jnp.square(x - mean), 0.0),
                  axis=1, keepdims=True) / h
    return mean, var


# --- forward ----------------------------------------------------------------

def _ln_fwd_kernel(h, eps, affine, x_ref, *rest):
    if affine:
        w_ref, b_ref, y_ref = rest
    else:
        (y_ref,) = rest
    x = x_ref[:].astype(jnp.float32)
    mask = _col_mask(h, x.shape[1], x.shape[0])
    mean, var = _moments(x, h, mask)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    if affine:
        y = y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = jnp.where(mask, y, 0.0).astype(y_ref.dtype)


def _ln_forward(x2, weight, bias, eps, block_rows=None):
    n, h = x2.shape
    hp = -(-h // LANES) * LANES
    if block_rows is None:
        from apex_tpu.ops import autotune
        block_rows = autotune.tuned_rows("layer_norm", (n, h), x2.dtype)
    r = block_rows if block_rows is not None else _row_block(hp, 4)
    npad = -(-n // r) * r
    xp = _pad2(x2, npad, hp)
    affine = weight is not None

    row_spec = pl.BlockSpec((r, hp), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    in_specs = [row_spec]
    args = [xp]
    if affine:
        wb_spec = pl.BlockSpec((1, hp), lambda i: (0, 0),
                               memory_space=pltpu.VMEM)
        in_specs += [wb_spec, wb_spec]
        args += [_pad2(weight.reshape(1, h), 1, hp),
                 _pad2(bias.reshape(1, h), 1, hp)]

    y = pallas_call(
        functools.partial(_ln_fwd_kernel, h, eps, affine),
        grid=(npad // r,),
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((npad, hp), x2.dtype),
        name="apex_layer_norm_fwd",
    )(*args)
    return y[:n, :h]


# --- backward ---------------------------------------------------------------

def _ln_bwd_kernel(h, eps, affine, g_ref, x_ref, *rest):
    if affine:
        w_ref, dx_ref, dw_ref, db_ref = rest
    else:
        (dx_ref,) = rest
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    mask = _col_mask(h, x.shape[1], x.shape[0])
    mean, var = _moments(x, h, mask)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd

    gw = g * w_ref[:].astype(jnp.float32) if affine else g
    gw = jnp.where(mask, gw, 0.0)
    # dgrad: rstd * (gw - mean(gw) - xhat * mean(gw*xhat))
    # (`cuComputeGradInput`, `layer_norm_cuda_kernel.cu:523-650`)
    m1 = jnp.sum(gw, axis=1, keepdims=True) / h
    m2 = jnp.sum(gw * xhat, axis=1, keepdims=True) / h
    dx = rstd * (gw - m1 - xhat * m2)
    dx_ref[:] = jnp.where(mask, dx, 0.0).astype(dx_ref.dtype)
    if affine:
        gm = jnp.where(mask, g, 0.0)
        # per-block partial reductions (`cuComputePartGradGammaBeta`),
        # written into row 0 of an 8-sublane slab: Mosaic requires the
        # block's second-to-last dim be a multiple of 8 (or the full
        # array dim), so a (1, hp) partial row per grid step is not a
        # legal block — the stage-2 sum absorbs the zero rows
        rows = jax.lax.broadcasted_iota(jnp.int32, dw_ref.shape, 0)
        dw_ref[:] = jnp.where(rows == 0,
                              jnp.sum(gm * xhat, axis=0, keepdims=True),
                              0.0)
        db_ref[:] = jnp.where(rows == 0,
                              jnp.sum(gm, axis=0, keepdims=True), 0.0)


def _ln_backward(g2, x2, weight, eps, block_rows=None):
    n, h = x2.shape
    hp = -(-h // LANES) * LANES
    if block_rows is None:
        from apex_tpu.ops import autotune
        block_rows = autotune.tuned_rows("layer_norm", (n, h), x2.dtype)
    r = block_rows if block_rows is not None else _row_block(hp, 6)
    npad = -(-n // r) * r
    nblocks = npad // r
    gp = _pad2(g2, npad, hp)
    xp = _pad2(x2, npad, hp)
    affine = weight is not None

    row_spec = pl.BlockSpec((r, hp), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    part_spec = pl.BlockSpec((8, hp), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)
    in_specs = [row_spec, row_spec]
    args = [gp, xp]
    out_specs = [row_spec]
    out_shapes = [jax.ShapeDtypeStruct((npad, hp), x2.dtype)]
    if affine:
        in_specs.append(pl.BlockSpec((1, hp), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        args.append(_pad2(weight.reshape(1, h), 1, hp))
        out_specs += [part_spec, part_spec]
        out_shapes += [jax.ShapeDtypeStruct((nblocks * 8, hp),
                                            jnp.float32)] * 2

    res = pallas_call(
        functools.partial(_ln_bwd_kernel, h, eps, affine),
        grid=(nblocks,),
        in_specs=in_specs,
        out_specs=tuple(out_specs) if affine else out_specs[0],
        out_shape=tuple(out_shapes) if affine else out_shapes[0],
        name="apex_layer_norm_bwd",
    )(*args)
    if affine:
        dx, dw_part, db_part = res
        # stage-2 reduction of the partials
        dw = jnp.sum(dw_part, axis=0)[:h]
        db = jnp.sum(db_part, axis=0)[:h]
        return dx[:n, :h], dw, db
    return res[:n, :h], None, None


# --- public API -------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm_affine(x, weight, bias, eps=1e-5):
    """LayerNorm over the last dim with affine params — the
    ``fused_layer_norm_affine`` entry (`apex/normalization/
    fused_layer_norm.py:12-69`). Weight/bias grads come back in fp32."""
    shape = x.shape
    y = _ln_forward(x.reshape(-1, shape[-1]), weight, bias, eps)
    return y.reshape(shape)


def _flna_fwd(x, weight, bias, eps):
    return fused_layer_norm_affine(x, weight, bias, eps), (x, weight)


def _flna_bwd(eps, res, g):
    x, weight = res
    shape = x.shape
    dx, dw, db = _ln_backward(g.reshape(-1, shape[-1]),
                              x.reshape(-1, shape[-1]), weight, eps)
    return (dx.reshape(shape), dw.astype(weight.dtype),
            db.astype(weight.dtype))


fused_layer_norm_affine.defvjp(_flna_fwd, _flna_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fused_layer_norm(x, eps=1e-5):
    """Non-affine LayerNorm (`fused_layer_norm.py:71-100`)."""
    shape = x.shape
    return _ln_forward(x.reshape(-1, shape[-1]), None, None,
                       eps).reshape(shape)


def _fln_fwd(x, eps):
    return fused_layer_norm(x, eps), x


def _fln_bwd(eps, x, g):
    shape = x.shape
    dx, _, _ = _ln_backward(g.reshape(-1, shape[-1]),
                            x.reshape(-1, shape[-1]), None, eps)
    return (dx.reshape(shape),)


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)


def layer_norm_reference(x, weight=None, bias=None, eps=1e-5):
    """Pure-jnp reference (the CPU fallback `F.layer_norm` path,
    `fused_layer_norm.py:57-62`) — also the numeric oracle in tests."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(x.dtype)


class FusedLayerNorm:
    """flax module mirror of ``apex.normalization.FusedLayerNorm``
    (`fused_layer_norm.py:70-165`)."""

    def __new__(cls, normalized_shape, eps=1e-5, elementwise_affine=True):
        import flax.linen as nn

        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        h = int(np.prod(normalized_shape))

        class _FusedLayerNorm(nn.Module):
            @nn.compact
            def __call__(self, x):
                if elementwise_affine:
                    w = self.param("scale", nn.initializers.ones, (h,),
                                   jnp.float32)
                    b = self.param("bias", nn.initializers.zeros, (h,),
                                   jnp.float32)
                    return fused_layer_norm_affine(x, w, b, eps)
                return fused_layer_norm(x, eps)

        return _FusedLayerNorm()
