"""Fused label-smoothing softmax cross-entropy.

TPU-native rebuild of `xentropy_cuda`
(`apex/contrib/csrc/xentropy/xentropy_kernel.cu:1-722`,
`apex/contrib/xentropy/softmax_xentropy.py:4-28`): one forward pass
computes per-row losses with in-kernel label smoothing, saving only the
log-sum-exp residual (the reference's ``max_log_sum_exp`` memory win — the
softmax output is never materialized); the backward kernel recomputes the
softmax from logits + lse in registers.

loss_i = lse_i − (1−ε)·x_i[y_i] − (ε/K)·Σ_j x_ij
dx_ij = g_i · (exp(x_ij − lse_i) − (1−ε)·1[j=y_i] − ε/K)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_call

LANES = 128


#: what a kernel's blocks and its float32 working copy of one may take of
#: the 16 MiB of scoped VMEM
VMEM_FITS = int(15.5 * 2 ** 20)


def _row_block(v_padded: int, n_bufs: int, itemsize: int = 4,
               rows: int = 0) -> int:
    """Rows per grid step: size the vocab-wide blocks to a ~6 MiB
    double-buffered budget over ``n_bufs`` logits-sized buffers of the
    actual ``itemsize`` (bf16 logits take 2-3x larger rows than the old
    fp32-assuming 1 MiB bound — per-step overhead amortizes over fewer,
    fatter steps; measured on the BERT-vocab shapes).

    A block's float32 working copy sits beside the buffers in scoped VMEM: a
    budget used to the last byte (256 bf16 rows of a power-of-two vocabulary
    of 8192) leaves it 384 KiB short. Only then the block shrinks: to the
    largest multiple of 16 that fits and divides ``rows`` (no padded copy of
    the logits), or, where none does, that fits."""
    r = (8 << 20) // (2 * n_bufs * itemsize * v_padded)
    r = max(16, min(256, (r // 16) * 16))
    fits = VMEM_FITS // ((2 * n_bufs * itemsize + 4) * v_padded) // 16 * 16
    if r <= fits:
        return r
    whole = [b for b in range(fits, 15, -16) if rows and rows % b == 0]
    return max(16, whole[0] if whole else fits)


def _pad2(x2, rows, cols):
    n, c = x2.shape
    if n == rows and c == cols:
        return x2
    return jnp.pad(x2, ((0, rows - n), (0, cols - c)))


def _fwd_kernel(v, smoothing, x_ref, lab_ref, loss_ref, lse_ref):
    x = x_ref[:].astype(jnp.float32)
    r, vp = x.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (r, vp), 1)
    # the lane block covers the vocab dim exactly (vp == v in
    # _fwd_call/_bwd_call), so the vocab-validity mask is statically
    # all-true and its where passes are elided — each is a full
    # (r, 30522)-class VPU sweep at BERT shapes
    padded = vp > v
    if padded:
        mask = cols < v
        x = jnp.where(mask, x, -jnp.inf)
    xmax = jnp.max(x, axis=1, keepdims=True)
    # padded lanes already hold -inf in x, so exp underflows to 0
    lse = xmax + jnp.log(jnp.sum(jnp.exp(x - xmax), axis=1,
                                 keepdims=True))
    labels = lab_ref[:, :1]                      # (r, 1) int32
    onehot = cols == labels
    x_label = jnp.sum(jnp.where(onehot, x, 0.0), axis=1, keepdims=True)
    loss = lse - (1.0 - smoothing) * x_label
    if smoothing:
        xs = jnp.where(mask, x, 0.0) if padded else x
        loss = loss - (smoothing / v) * jnp.sum(
            xs, axis=1, keepdims=True)
    # ignored rows (label < 0) produce zero loss (padding convention)
    valid = labels >= 0
    loss_ref[:] = jnp.where(valid, loss, 0.0) + jnp.zeros((r, LANES),
                                                          jnp.float32)
    lse_ref[:] = lse + jnp.zeros((r, LANES), jnp.float32)


def _bwd_kernel(v, smoothing, x_ref, lab_ref, lse_ref, g_ref, dx_ref):
    x = x_ref[:].astype(jnp.float32)
    r, vp = x.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (r, vp), 1)
    labels = lab_ref[:, :1]
    lse = lse_ref[:, :1]
    g = g_ref[:, :1]
    prob = jnp.exp(x - lse)
    target = (1.0 - smoothing) * (cols == labels)
    if smoothing:
        target = target + smoothing / v
    if vp > v:                   # vp == v by construction; see _fwd_call
        mask = cols < v
        prob = jnp.where(mask, prob, 0.0)
        if smoothing:
            target = jnp.where(mask, target, 0.0)
    dx = g * (prob - target)
    dx = jnp.where(labels >= 0, dx, 0.0)
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _broadcast_lanes(vec, npad):
    out = jnp.zeros((npad,), vec.dtype).at[:vec.shape[0]].set(vec)
    return jnp.broadcast_to(out[:, None], (npad, LANES))


def _fwd_call(x2, labels, smoothing, block_rows=None):
    n, v = x2.shape
    # lane dim = the full vocab dim (legal for Mosaic whatever v is) —
    # padding V up to a 128 multiple would copy the whole logits tensor
    # (500 MB at BERT vocab) just to round 30522 → 30592
    vp = v
    if block_rows is None:
        from apex_tpu.ops import autotune
        block_rows = autotune.tuned_rows("xentropy", (n, v), x2.dtype)
    r = (block_rows if block_rows is not None
         else _row_block(-(-v // LANES) * LANES, 1, x2.dtype.itemsize, n))
    npad = -(-n // r) * r
    xp = _pad2(x2, npad, vp)
    # padding rows get label -1 → zero loss
    lab = _broadcast_lanes(
        jnp.where(jnp.arange(npad) < n,
                  jnp.pad(labels.astype(jnp.int32), (0, npad - n)),
                  -1), npad)

    row = pl.BlockSpec((r, vp), lambda i: (i, 0), memory_space=pltpu.VMEM)
    lane = pl.BlockSpec((r, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    loss, lse = pallas_call(
        functools.partial(_fwd_kernel, v, smoothing),
        grid=(npad // r,),
        in_specs=[row, lane],
        out_specs=(lane, lane),
        out_shape=(jax.ShapeDtypeStruct((npad, LANES), jnp.float32),) * 2,
        name="apex_xentropy_fwd",
    )(xp, lab)
    return loss[:n, 0], lse[:n, 0]


def _bwd_call(x2, labels, lse, g, smoothing, block_rows=None):
    n, v = x2.shape
    vp = v                      # full-dim lane blocks; see _fwd_call
    if block_rows is None:
        from apex_tpu.ops import autotune
        block_rows = autotune.tuned_rows("xentropy", (n, v), x2.dtype)
    r = (block_rows if block_rows is not None
         else _row_block(-(-v // LANES) * LANES, 2, x2.dtype.itemsize, n))
    npad = -(-n // r) * r
    xp = _pad2(x2, npad, vp)
    lab = _broadcast_lanes(
        jnp.where(jnp.arange(npad) < n,
                  jnp.pad(labels.astype(jnp.int32), (0, npad - n)),
                  -1), npad)
    lsep = _broadcast_lanes(lse, npad)
    gp = _broadcast_lanes(g.astype(jnp.float32), npad)

    row = pl.BlockSpec((r, vp), lambda i: (i, 0), memory_space=pltpu.VMEM)
    lane = pl.BlockSpec((r, LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    dx = pallas_call(
        functools.partial(_bwd_kernel, v, smoothing),
        grid=(npad // r,),
        in_specs=[row, lane, lane, lane],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((npad, vp), x2.dtype),
        name="apex_xentropy_bwd",
    )(xp, lab, lsep, gp)
    return dx[:n, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_cross_entropy_loss(logits, labels, smoothing=0.0):
    """Per-example losses, fused. ``logits`` (..., V), int ``labels``
    (...,); rows with negative labels contribute zero loss/grad. The
    callable mirror of ``SoftmaxCrossEntropyLoss.apply``
    (`apex/contrib/xentropy/softmax_xentropy.py:4-28`)."""
    shape = logits.shape[:-1]
    loss, _ = _fwd_call(logits.reshape(-1, logits.shape[-1]),
                        labels.reshape(-1), smoothing)
    return loss.reshape(shape)


def _sce_fwd(logits, labels, smoothing):
    x2 = logits.reshape(-1, logits.shape[-1])
    lab = labels.reshape(-1)
    loss, lse = _fwd_call(x2, lab, smoothing)
    return loss.reshape(labels.shape), (logits, labels, lse)


def _sce_bwd(smoothing, res, g):
    logits, labels, lse = res
    dx = _bwd_call(logits.reshape(-1, logits.shape[-1]),
                   labels.reshape(-1), lse, g.reshape(-1), smoothing)
    return dx.reshape(logits.shape), None


softmax_cross_entropy_loss.defvjp(_sce_fwd, _sce_bwd)


def softmax_cross_entropy_reference(logits, labels, smoothing=0.0):
    """Pure-jnp oracle for tests (`test_label_smoothing.py`'s local
    reference)."""
    x = logits.astype(jnp.float32)
    v = x.shape[-1]
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), v)
    x_label = jnp.sum(x * onehot, axis=-1)
    loss = lse - (1 - smoothing) * x_label - smoothing / v * jnp.sum(
        x, axis=-1)
    return jnp.where(labels >= 0, loss, 0.0)
