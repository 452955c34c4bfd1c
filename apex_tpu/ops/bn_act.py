"""Fused BatchNorm → (+residual) → ReLU with a minimal-residual VJP.

TPU rebuild of the reference's fused-BN CUDA family — the persistent
NHWC BN kernels (`apex/contrib/csrc/groupbn/nhwc_batch_norm_kernel.h`),
the add+relu fusion (`batch_norm_add_relu.cu`) and the hand-written
backward reductions (`csrc/welford.cu:259-903`). Those kernels exist to
cut HBM traffic: BN-backward under plain autodiff re-reads saved
activations several times (flax saves the input *and* x̂ *and* the relu
source), and on a memory-bound model that traffic is the MFU ceiling
(see PERF.md: the measured 80 GB/step vs the ~45 GB ideal graph).

The TPU answer is not a persistent kernel but *residual control*: one
``jax.custom_vjp`` unit covering BN → (+residual) → ReLU whose backward

- saves only the conv output ``x`` (already materialized in HBM — XLA
  dedups it with the copy the forward consumes) plus per-channel
  ``(mean, invstd)`` and, for the add+relu variant, the unit output
  ``z`` (also already saved: it is the next conv's input);
- recomputes ``x̂`` and the ReLU mask in-register instead of re-reading
  saved intermediates (`x̂γ+β > 0` for plain BN+ReLU, ``z > 0`` for the
  residual join);
- emits exactly the two irreducible HBM passes over ``(x, dy)``: one
  channel-sum reduce (Σdy, Σdy·x̂ — the `reduce_bn` stage of
  `optimized_sync_batchnorm_kernel.py:77-119`) and one elementwise dx
  pass.

Cross-device statistics (SyncBN / groupbn semantics) ride the same unit:
the forward combines per-device moments over ``axis_name`` (Welford,
exact for the stats-group case) and the backward ``psum``s the two
channel sums — the hand-derived collectives of the reference's SyncBN
backward, placed explicitly because autodiff no longer sees the stats.

Gradient note: the ``(mean, var, count)`` outputs exist for running-stat
EMA updates and are treated as ``stop_gradient`` — cotangents flowing
into them are ignored, matching torch BN semantics where running stats
are buffers.

Tried and deleted (PR 30): a two-kernel Pallas backward and float8 x̂
residuals. Both lost on the chip; the numbers are in PERF.md §6.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

__all__ = ["bn_act_train", "bn_add_act_train", "bn_act_reference",
           "FusedBNAct"]


class _Cfg(NamedTuple):
    """Static configuration (hashable — custom_vjp nondiff arg)."""
    relu: bool
    eps: float
    axis_name: Optional[str]
    groups: Optional[Tuple[Tuple[int, ...], ...]]


def _normalize_groups(axis_index_groups):
    if axis_index_groups is None:
        return None
    return tuple(tuple(int(i) for i in g) for g in axis_index_groups)


def _reduce_axes(x):
    return tuple(range(x.ndim - 1))  # channels-last (TPU-native NHWC)


def _local_count(x) -> float:
    n = 1
    for s in x.shape[:-1]:
        n *= s
    return float(n)


def _stats(x32, cfg: _Cfg):
    """Per-channel (mean, biased var, count), combined over the stats
    group when ``cfg.axis_name`` is set (count-weighted Welford — the
    `welford_parallel` combine, `csrc/welford.cu:905-1000`).

    Local moments are ONE-pass (E[x²]−E[x]², f32 accumulation over the
    half input): both channel sums fuse into the producing conv's
    epilogue, so the stats cost no standalone HBM pass. A two-pass
    centered variance cannot fuse there (the mean must complete first)
    and measured +13 GB/step on the ResNet-50 bench. f32 accumulation
    over BN-scale activations keeps the cancellation benign — the same
    trade cudnn's persistent BN kernels make; the *cross-device* combine
    still uses the stable Welford form."""
    axes = _reduce_axes(x32)
    mean = jnp.mean(x32, axis=axes)
    var = jnp.maximum(jnp.mean(jnp.square(x32), axis=axes)
                      - jnp.square(mean), 0.0)
    count = jnp.float32(_local_count(x32))
    if cfg.axis_name is None:
        return mean, var, count
    from apex_tpu.parallel.sync_batchnorm import _welford_combine
    means = jax.lax.all_gather(mean, cfg.axis_name,
                               axis_index_groups=cfg.groups)
    variances = jax.lax.all_gather(var, cfg.axis_name,
                                   axis_index_groups=cfg.groups)
    counts = jax.lax.all_gather(count, cfg.axis_name,
                                axis_index_groups=cfg.groups)
    return _welford_combine(means, variances, counts)


def _apply(x32, r, scale, bias, mean, invstd, relu):
    y = (x32 - mean) * (invstd * scale.astype(jnp.float32)) \
        + bias.astype(jnp.float32)
    if r is not None:
        y = y + r.astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y


def _fwd_common(x, r, scale, bias, cfg: _Cfg):
    x32 = x.astype(jnp.float32)
    mean, var, count = _stats(x32, cfg)
    invstd = jax.lax.rsqrt(var + cfg.eps)
    z = _apply(x32, r, scale, bias, mean, invstd, cfg.relu).astype(x.dtype)
    return z, mean, var, count, invstd


def _bwd(cfg: _Cfg, x, scale, bias, mean, invstd, count, z, dz,
         has_residual: bool, r_dtype=None):
    """The two-pass minimal backward. Reads: (x, g-source) twice; writes
    dx[, dr]. x̂ is recomputed, never re-read.

    Mask handling is deliberately single-use so XLA cannot CSE it into a
    materialized pred tensor (measured: +6 GB/step on the bench when it
    does): for the residual join the mask folds into producing ``dr`` —
    an obligatory output — and the sums/dx passes then read ``dr``
    instead of (dz, z); for plain BN+ReLU the mask is recomputed from
    x̂γ+β inside each pass's fusion.
    """
    axes = _reduce_axes(x)
    cshape = (1,) * len(axes) + (-1,)
    mean_b = mean.reshape(cshape)
    invstd_b = invstd.reshape(cshape)
    scale32 = scale.astype(jnp.float32)

    def xhat_of(xv):
        return (xv.astype(jnp.float32) - mean_b) * invstd_b

    dr = None
    if cfg.relu and has_residual:
        # the unit output is the saved relu result (and the next conv's
        # input): z > 0 IS the mask. dr materializes ONCE (it is a
        # returned cotangent); everything downstream reads dr.
        dr = jnp.where(z > 0, dz, jnp.zeros((), dz.dtype)) \
            .astype(r_dtype if r_dtype is not None else dz.dtype)
        g_src = dr
    else:
        g_src = dz

    def masked(gv):
        g32 = gv.astype(jnp.float32)
        if cfg.relu and not has_residual:
            m = (xhat_of(x) * scale32.reshape(cshape)
                 + bias.astype(jnp.float32).reshape(cshape)) > 0
            g32 = jnp.where(m, g32, 0.0)
        return g32

    # pass 1: channel sums (fuses into one reduce over (x, g_src))
    g1 = masked(g_src)
    sum_dy = jnp.sum(g1, axis=axes)
    sum_dy_xhat = jnp.sum(g1 * xhat_of(x), axis=axes)
    if cfg.axis_name is not None:
        # the collectives the reference's hand-written SyncBN backward
        # issues (`optimized_sync_batchnorm_kernel.py:98-110`)
        sum_dy, sum_dy_xhat = jax.lax.psum(
            (sum_dy, sum_dy_xhat), cfg.axis_name,
            axis_index_groups=cfg.groups)

    # pass 2: dx (one elementwise fusion over (x, g_src))
    k1 = (sum_dy / count).reshape(cshape)
    k2 = (sum_dy_xhat / count).reshape(cshape)
    g2 = masked(g_src)
    xhat2 = xhat_of(x)
    dx = ((scale32 * invstd).reshape(cshape)
          * (g2 - k1 - xhat2 * k2)).astype(x.dtype)
    dscale = sum_dy_xhat.astype(scale.dtype)
    dbias = sum_dy.astype(bias.dtype)
    if has_residual:
        if dr is None:          # no relu in the unit: identity add
            dr = dz.astype(r_dtype if r_dtype is not None else dz.dtype)
        return dx, dr, dscale, dbias
    return dx, dscale, dbias


# --- plain BN (+ReLU) --------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def bn_act_train(x, scale, bias, cfg: _Cfg):
    """Training-mode ``relu?(bn(x))`` over channels-last ``x``.

    Returns ``(z, mean, biased_var, count)``; the stat outputs are
    non-differentiable (running-stat feed). Build ``cfg`` via
    :func:`make_cfg`.
    """
    z, mean, var, count, _ = _fwd_common(x, None, scale, bias, cfg)
    return z, mean, var, count


def _bn_act_fwd(x, scale, bias, cfg):
    z, mean, var, count, invstd = _fwd_common(x, None, scale, bias, cfg)
    return (z, mean, var, count), (x, scale, bias, mean, invstd, count)


def _bn_act_bwd(cfg, res, cts):
    dz = cts[0]  # stat cotangents dropped: stats are buffers
    x, scale, bias, mean, invstd, count = res
    return _bwd(cfg, x, scale, bias, mean, invstd, count, None, dz,
                has_residual=False)


bn_act_train.defvjp(_bn_act_fwd, _bn_act_bwd)


# --- BN + residual add (+ReLU) ----------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def bn_add_act_train(x, r, scale, bias, cfg: _Cfg):
    """Training-mode ``relu?(bn(x) + r)`` — the residual-join unit
    (`batch_norm_add_relu.cu` semantics). Returns
    ``(z, mean, biased_var, count)``."""
    z, mean, var, count, _ = _fwd_common(x, r, scale, bias, cfg)
    return z, mean, var, count


def _bn_add_act_fwd(x, r, scale, bias, cfg):
    z, mean, var, count, invstd = _fwd_common(x, r, scale, bias, cfg)
    # z doubles as the relu mask source; it is consumed downstream (next
    # conv input) so saving it adds no HBM tensor
    zres = z if cfg.relu else None
    rtok = jnp.zeros((), r.dtype)  # dtype token (residual leaves: arrays)
    return (z, mean, var, count), (x, scale, bias, mean, invstd, count,
                                   zres, rtok)


def _bn_add_act_bwd(cfg, res, cts):
    dz = cts[0]
    x, scale, bias, mean, invstd, count, z, rtok = res
    return _bwd(cfg, x, scale, bias, mean, invstd, count, z, dz,
                has_residual=True, r_dtype=rtok.dtype)


bn_add_act_train.defvjp(_bn_add_act_fwd, _bn_add_act_bwd)


def make_cfg(*, relu: bool, eps: float = 1e-5,
             axis_name: Optional[str] = None,
             axis_index_groups=None) -> _Cfg:
    return _Cfg(relu=bool(relu), eps=float(eps), axis_name=axis_name,
                groups=_normalize_groups(axis_index_groups))


def bn_act_reference(x, scale, bias, *, residual=None, relu=True,
                     eps=1e-5):
    """Pure-jnp oracle (plain autodiff path) for tests."""
    x32 = x.astype(jnp.float32)
    axes = _reduce_axes(x)
    mean = jnp.mean(x32, axis=axes)
    var = jnp.mean(jnp.square(x32 - mean.reshape((1,) * len(axes) + (-1,))),
                   axis=axes)
    invstd = jax.lax.rsqrt(var + eps)
    y = _apply(x32, residual, scale, bias, mean, invstd, relu)
    return y.astype(x.dtype), mean, var


# --- flax module -------------------------------------------------------------

class FusedBNAct(nn.Module):
    """BatchNorm with optionally fused residual-add and ReLU, channels
    last, minimal-residual backward — the module surface of the
    reference's `BatchNorm2d_NHWC(fuse_relu=...)`
    (`apex/contrib/groupbn/batch_norm.py:18-90`) and the BN units inside
    the imagenet example's ResNet.

    Parameters/statistics are fp32 regardless of the activation dtype
    (keep_batchnorm_fp32); activations pass through in ``dtype``.
    Running stats follow the torch convention (unbiased var EMA), with
    the flax momentum convention ``ra = m·ra + (1−m)·new``.
    """
    num_features: int
    relu: bool = True
    momentum: float = 0.9
    epsilon: float = 1e-5
    axis_name: Optional[str] = None
    axis_index_groups: Optional[Sequence[Sequence[int]]] = None
    init_scale: float = 1.0
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, residual=None, train: bool = True):
        c = self.num_features
        if self.dtype is not None:
            x = x.astype(self.dtype)
            if residual is not None:
                residual = residual.astype(self.dtype)
        scale = self.param("scale",
                           nn.initializers.constant(self.init_scale),
                           (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda *_: jnp.zeros((c,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda *_: jnp.ones((c,), jnp.float32))

        if not train:
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon)
            y = _apply(x.astype(jnp.float32), residual, scale, bias,
                       ra_mean.value, inv, self.relu)
            return y.astype(x.dtype)

        axis = None if self.is_initializing() else self.axis_name
        cfg = make_cfg(relu=self.relu, eps=self.epsilon, axis_name=axis,
                       axis_index_groups=self.axis_index_groups)
        if residual is None:
            z, mean, var, count = bn_act_train(x, scale, bias, cfg)
        else:
            z, mean, var, count = bn_add_act_train(x, residual, scale,
                                                   bias, cfg)

        if not self.is_initializing():
            unbiased = var * count / jnp.maximum(count - 1.0, 1.0)
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1 - m) * mean
            ra_var.value = m * ra_var.value + (1 - m) * unbiased
        return z
