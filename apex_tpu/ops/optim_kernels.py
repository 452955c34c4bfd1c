"""Fused optimizer update kernels over the flat arena.

TPU-native rebuild of the reference's optimizer functors
(`csrc/multi_tensor_adam.cu:24-120`, `multi_tensor_sgd_kernel.cu:30-180`,
`multi_tensor_adagrad.cu`, `multi_tensor_lamb.cu:41-320`,
`multi_tensor_novograd.cu`): one Pallas kernel pass updates every parameter
of a dtype partition — parameters, gradients and optimizer state are flat
1-D buffers (apex_tpu.arena), walked in (512, 128) VMEM blocks.

Algorithm flags (adam_w, nesterov, ...) are *static* — each combination
compiles a specialized kernel, like the reference's template instantiations.
Runtime scalars (lr, betas, step count, grad scale) ride in SMEM so learning
rate schedules don't trigger recompilation.

All kernels compute in fp32 regardless of storage dtype and can emit an
additional low-precision parameter copy in the same pass (the reference's
depth-4 SGD / `reversible_adam` p_copy outputs, used to keep fp16 model
params in sync with fp32 masters at zero extra bandwidth).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import launch


def _launch(name, kernel, inputs, out_dtypes, scalars):
    """Elementwise arena kernel via the shared launcher: all outputs are
    full block buffers."""
    return launch(kernel, inputs, outs=[("block", dt) for dt in out_dtypes],
                  scalars=scalars, name=name)


# --- Adam / AdamW (`multi_tensor_adam.cu:24-120`) ---------------------------

def _adam_kernel(adam_w, has_copy, scalars, p_ref, g_ref, m_ref, v_ref,
                 po_ref, mo_ref, vo_ref, *copy_ref):
    lr, b1, b2, eps, wd, bc1, bc2, gscale = (scalars[i] for i in range(8))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    if not adam_w:           # L2-regularization mode: wd folded into grad
        g = g + wd * p
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    m_hat = m / bc1
    v_hat = v / bc2
    update = m_hat / (jnp.sqrt(v_hat) + eps)
    if adam_w:               # decoupled weight decay
        update = update + wd * p
    p = p - lr * update

    po_ref[:] = p.astype(po_ref.dtype)
    mo_ref[:] = m.astype(mo_ref.dtype)
    vo_ref[:] = v.astype(vo_ref.dtype)
    if has_copy:
        copy_ref[0][:] = p.astype(copy_ref[0].dtype)


def adam_update(p, g, m, v, *, lr, beta1, beta2, eps, weight_decay, step,
                adam_w_mode=True, bias_correction=True, grad_scale=1.0,
                param_copy_dtype=None):
    """One fused Adam/AdamW step over a flat partition.

    ``step`` is the 1-based step count *after* increment (traced ok).
    Returns (p, m, v) or (p, m, v, p_copy) when ``param_copy_dtype`` is set.
    """
    step = jnp.asarray(step, jnp.float32)
    if bias_correction:
        bc1 = 1.0 - jnp.power(jnp.float32(beta1), step)
        bc2 = 1.0 - jnp.power(jnp.float32(beta2), step)
    else:
        bc1 = bc2 = jnp.float32(1.0)
    scalars = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                         (lr, beta1, beta2, eps, weight_decay, bc1, bc2,
                          grad_scale)])
    out_dtypes = [p.dtype, m.dtype, v.dtype]
    if param_copy_dtype is not None:
        out_dtypes.append(jnp.dtype(param_copy_dtype))
    kernel = functools.partial(_adam_kernel, adam_w_mode,
                               param_copy_dtype is not None)
    return _launch("apex_rows_adam", kernel, [p, g, m, v], out_dtypes,
                   scalars)


# --- SGD (`multi_tensor_sgd_kernel.cu:30-180`) ------------------------------

def _sgd_kernel(nesterov, wd_after_momentum, has_copy,
                scalars, p_ref, g_ref, m_ref, po_ref, mo_ref, *copy_ref):
    lr, momentum, dampening, wd, gscale, first = (
        scalars[i] for i in range(6))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale
    m = m_ref[:].astype(jnp.float32)

    if not wd_after_momentum:
        g = g + wd * p
    # first step: momentum buffer initialized to the raw gradient (PyTorch
    # semantics the reference's `first_run` flag reproduces). Runtime scalar
    # so the step counter stays traced.
    m = jnp.where(first > 0.5, g, momentum * m + (1.0 - dampening) * g)
    upd = (g + momentum * m) if nesterov else m
    if wd_after_momentum:
        upd = upd + wd * p
    p = p - lr * upd

    po_ref[:] = p.astype(po_ref.dtype)
    mo_ref[:] = m.astype(mo_ref.dtype)
    if has_copy:
        copy_ref[0][:] = p.astype(copy_ref[0].dtype)


def sgd_update(p, g, m, *, lr, momentum=0.0, dampening=0.0, weight_decay=0.0,
               nesterov=False, first_run=False, wd_after_momentum=False,
               grad_scale=1.0, param_copy_dtype=None):
    """Fused SGD with momentum. ``first_run`` (traced or static) initializes
    the momentum buffer inside the kernel (`fused_sgd.py:128-216`
    semantics). The optional ``param_copy_dtype`` output is the depth-4 mode
    (master step + model copy in one pass)."""
    first = jnp.asarray(first_run, jnp.float32)
    scalars = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                         (lr, momentum, dampening, weight_decay, grad_scale)]
                        + [first])
    out_dtypes = [p.dtype, m.dtype]
    if param_copy_dtype is not None:
        out_dtypes.append(jnp.dtype(param_copy_dtype))
    kernel = functools.partial(_sgd_kernel, nesterov, wd_after_momentum,
                               param_copy_dtype is not None)
    return _launch("apex_rows_sgd", kernel, [p, g, m], out_dtypes, scalars)


# --- Adagrad (`multi_tensor_adagrad.cu`) ------------------------------------

def _adagrad_kernel(adagrad_w, scalars, p_ref, g_ref, h_ref, po_ref, ho_ref):
    lr, eps, wd, gscale = (scalars[i] for i in range(4))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * gscale
    h = h_ref[:].astype(jnp.float32)

    if not adagrad_w:
        g = g + wd * p
    h = h + g * g
    upd = g / (jnp.sqrt(h) + eps)
    if adagrad_w:            # decoupled decay
        upd = upd + wd * p
    p = p - lr * upd

    po_ref[:] = p.astype(po_ref.dtype)
    ho_ref[:] = h.astype(ho_ref.dtype)


def adagrad_update(p, g, h, *, lr, eps=1e-10, weight_decay=0.0,
                   adagrad_w_mode=False, grad_scale=1.0):
    scalars = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                         (lr, eps, weight_decay, grad_scale)])
    kernel = functools.partial(_adagrad_kernel, adagrad_w_mode)
    return _launch("apex_rows_adagrad", kernel, [p, g, h],
                   [p.dtype, h.dtype], scalars)


# --- LAMB, two-stage (`multi_tensor_lamb.cu:41,234`) ------------------------

def _lamb_stage1_kernel(adam_w, scalars, p_ref, g_ref, m_ref, v_ref,
                        u_ref, mo_ref, vo_ref):
    b1, b2, eps, wd, bc1, bc2, clip, b3 = (scalars[i] for i in range(8))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * clip   # global-norm clip folded in
    m = m_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    if not adam_w:
        g = g + wd * p
    m = b1 * m + b3 * g
    v = b2 * v + (1.0 - b2) * g * g
    u = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if adam_w:
        u = u + wd * p

    u_ref[:] = u
    mo_ref[:] = m.astype(mo_ref.dtype)
    vo_ref[:] = v.astype(vo_ref.dtype)


def lamb_stage1(p, g, m, v, *, beta1, beta2, eps, weight_decay, step,
                bias_correction=True, adam_w_mode=True, clip_scale=1.0,
                grad_averaging=True):
    """Stage 1: Adam-style update direction ``u`` (fp32) + new m, v.

    ``clip_scale`` pre-scales grads by ``max_grad_norm/global_norm`` when
    clipping is active (the reference computes the global norm with
    `multi_tensor_l2norm` first, `fused_lamb.py:120-136`).
    ``grad_averaging=False`` accumulates raw grads into the first moment
    (``m = β1·m + g`` instead of ``β1·m + (1−β1)·g``) — the reference's
    ``grad_averaging`` knob (`multi_tensor_lamb.cu:60-63`, the same
    ``beta3`` NovoGrad exposes)."""
    step = jnp.asarray(step, jnp.float32)
    if bias_correction:
        bc1 = 1.0 - jnp.power(jnp.float32(beta1), step)
        bc2 = 1.0 - jnp.power(jnp.float32(beta2), step)
    else:
        bc1 = bc2 = jnp.float32(1.0)
    b3 = (1.0 - beta1) if grad_averaging else 1.0
    scalars = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                         (beta1, beta2, eps, weight_decay, bc1, bc2,
                          clip_scale, b3)])
    kernel = functools.partial(_lamb_stage1_kernel, adam_w_mode)
    return _launch("apex_rows_lamb_stage1", kernel, [p, g, m, v],
                   [jnp.float32, m.dtype, v.dtype], scalars)


def _lamb_stage2_kernel(has_copy, scalars, p_ref, u_ref, r_ref,
                        po_ref, *copy_ref):
    lr = scalars[0]
    p = p_ref[:].astype(jnp.float32)
    u = u_ref[:]
    r = r_ref[:]                       # per-position trust ratio
    p = p - lr * r * u
    po_ref[:] = p.astype(po_ref.dtype)
    if has_copy:
        copy_ref[0][:] = p.astype(copy_ref[0].dtype)


def lamb_stage2(p, u, ratio_per_pos, *, lr, param_copy_dtype=None):
    """Stage 2: apply ``p -= lr * trust_ratio * u``; the trust ratio is
    gathered per arena position from per-tensor norms computed between the
    stages (`multi_tensor_lamb.cu:234-320`)."""
    scalars = jnp.stack([jnp.asarray(lr, jnp.float32)])
    out_dtypes = [p.dtype]
    if param_copy_dtype is not None:
        out_dtypes.append(jnp.dtype(param_copy_dtype))
    kernel = functools.partial(_lamb_stage2_kernel,
                               param_copy_dtype is not None)
    return _launch("apex_rows_lamb_stage2", kernel, [p, u, ratio_per_pos],
                   out_dtypes, scalars)


# --- NovoGrad (`multi_tensor_novograd.cu:24-130`) ---------------------------

def _novograd_kernel(reg_inside_moment, scalars, p_ref, g_ref, m_ref,
                     vpos_ref, po_ref, mo_ref):
    lr, b1, b3, eps, wd, bc1, bc2 = (scalars[i] for i in range(7))
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:].astype(jnp.float32)
    vnorm = vpos_ref[:]                # per-position per-layer norm EMA

    denom = vnorm / bc2 + eps
    if reg_inside_moment:
        # MOMENT_MODE_0: normalize + decay inside the momentum
        g = g / denom + wd * p
        m = b1 * m + b3 * g
        p = p - lr * (m / bc1)
    else:
        # MOMENT_MODE_1 (reference default): raw-grad momentum, decoupled
        # decay at update time (`multi_tensor_novograd.cu:107-112`)
        m = b1 * m + b3 * g
        update = (m / bc1) / denom + wd * p
        p = p - lr * update
    po_ref[:] = p.astype(po_ref.dtype)
    mo_ref[:] = m.astype(mo_ref.dtype)


def novograd_update(p, g, m, vnorm_per_pos, *, lr, beta1, beta2, eps,
                    weight_decay, step, grad_averaging=True,
                    bias_correction=True, reg_inside_moment=False):
    """NovoGrad elementwise stage. The per-layer norm EMAs (a
    (num_tensors,) vector — the reference's ``exp_avg_sq`` buffer, which
    stores *norms*, not squares, `fused_novograd.py:157-174`) are maintained
    outside and broadcast per position. bc2 = sqrt(1-beta2^t) matches the
    reference's correction of the norm (`multi_tensor_novograd.cu:148-152`)."""
    b3 = (1.0 - beta1) if grad_averaging else 1.0
    step = jnp.asarray(step, jnp.float32)
    if bias_correction:
        bc1 = 1.0 - jnp.power(jnp.float32(beta1), step)
        bc2 = jnp.sqrt(1.0 - jnp.power(jnp.float32(beta2), step))
    else:
        bc1 = bc2 = jnp.float32(1.0)
    scalars = jnp.stack([jnp.asarray(s, jnp.float32) for s in
                         (lr, beta1, b3, eps, weight_decay)] + [bc1, bc2])
    kernel = functools.partial(_novograd_kernel, reg_inside_moment)
    return _launch("apex_rows_novograd", kernel, [p, g, m, vnorm_per_pos],
                   [p.dtype, m.dtype], scalars)
