"""Fused MLP — the whole Linear(+bias)(+activation) stack in one kernel.

TPU-native rebuild of `mlp_cuda` (`csrc/mlp.cpp:1-164`,
`csrc/mlp_cuda.cu:55-780`): the reference loops cuBLAS GEMMs with fused
bias/ReLU/sigmoid epilogue kernels and one shared workspace. Here a single
Pallas kernel walks row blocks of the batch with *every layer's weights
resident in VMEM*, so inter-layer activations never touch HBM — the TPU
version of the reference's workspace reuse, and strictly more fused than
its per-layer GEMM launches.

When the weights don't fit the VMEM budget the op falls back to a jnp
chain, which XLA still fuses (bias+activation ride the MXU epilogue) —
matching the reference's "no extension" fallback semantics with no
capability loss.

Backward is the XLA autodiff of the reference chain: plain GEMMs are
exactly what the MXU + XLA already schedule optimally, so a hand-written
Pallas backward would only re-derive `mlp_cuda.backward`'s dgrad/wgrad
GEMM loop (`mlp_cuda.cu:440-780`).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import pallas_call

LANES = 128
_VMEM_WEIGHT_BUDGET = 8 << 20  # bytes of fp32 weights resident per step

_ACTS = {
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "sigmoid": jax.nn.sigmoid,
}


def _pad_to(x, rows, cols):
    r, c = x.shape
    if r == rows and c == cols:
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


def _mlp_kernel(num_layers, activation, use_bias, x_ref, *refs):
    w_refs = refs[:num_layers]
    b_refs = refs[num_layers:2 * num_layers] if use_bias else ()
    y_ref = refs[-1]
    act = _ACTS[activation]
    h = x_ref[:].astype(jnp.float32)
    for i in range(num_layers):
        h = jnp.dot(h, w_refs[i][:].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
        if use_bias:
            h = h + b_refs[i][:].astype(jnp.float32)
        if i < num_layers - 1 or activation != "none":
            h = act(h)
    y_ref[:] = h.astype(y_ref.dtype)


def mlp_reference(x, weights, biases=None, activation="relu"):
    """jnp chain oracle — `nn.Sequential(Linear...)` in the reference tests
    (`tests/L0/run_mlp/test_mlp.py`). Activation applies after every layer
    including the last, matching `mlp_cuda.forward` (`csrc/mlp.cpp:30-60`).
    """
    act = _ACTS[activation]
    h = x
    for i, w in enumerate(weights):
        h = jnp.dot(h, w, preferred_element_type=jnp.float32).astype(x.dtype)
        if biases is not None:
            h = h + biases[i].astype(h.dtype)
        h = act(h) if activation != "none" else h
    return h


def _weights_fit_vmem(weights) -> bool:
    total = sum(int(np.prod(w.shape)) * 4 for w in weights)
    return total <= _VMEM_WEIGHT_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_mlp(x, weights, biases, activation="relu"):
    """Whole-MLP forward: ``x @ W0 (+b0) act @ W1 (+b1) act ...``.

    ``weights``: tuple of (Din, Dout) matrices; ``biases``: matching tuple
    or None. The public mirror of ``mlp_cuda.forward`` via ``MLP``
    (`apex/mlp/mlp.py:8-58`).
    """
    return _fused_mlp_fwd_impl(x, weights, biases, activation)


def _fused_mlp_fwd_impl(x, weights, biases, activation, block_rows=None):
    use_bias = biases is not None
    if not _weights_fit_vmem(weights):
        return mlp_reference(x, weights, biases, activation)

    lead = x.shape[:-1]
    d0 = x.shape[-1]
    x2 = x.reshape(-1, d0)
    n = x2.shape[0]
    dims = [d0] + [w.shape[1] for w in weights]
    if block_rows is None:
        from apex_tpu.ops import autotune
        block_rows = autotune.tuned_rows("mlp", (n, *dims), x.dtype)
    pdims = [-(-d // LANES) * LANES for d in dims]
    widest = max(pdims)
    r = (block_rows if block_rows is not None
         else max(16, min(256, ((1 << 20) // (4 * widest) // 16) * 16)))
    npad = -(-n // r) * r

    args = [_pad_to(x2, npad, pdims[0])]
    in_specs = [pl.BlockSpec((r, pdims[0]), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)]
    for li, w in enumerate(weights):
        args.append(_pad_to(w, pdims[li], pdims[li + 1]))
        in_specs.append(pl.BlockSpec(
            (pdims[li], pdims[li + 1]), lambda i: (0, 0),
            memory_space=pltpu.VMEM))
    if use_bias:
        for li, b in enumerate(biases):
            args.append(_pad_to(b.reshape(1, -1), 1, pdims[li + 1]))
            in_specs.append(pl.BlockSpec((1, pdims[li + 1]),
                                         lambda i: (0, 0),
                                         memory_space=pltpu.VMEM))

    y = pallas_call(
        functools.partial(_mlp_kernel, len(weights), activation, use_bias),
        grid=(npad // r,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((r, pdims[-1]), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((npad, pdims[-1]), x.dtype),
        name="apex_mlp_fwd",
    )(*args)
    return y[:n, :dims[-1]].reshape(*lead, dims[-1])


def _mlp_fwd(x, weights, biases, activation):
    return _fused_mlp_fwd_impl(x, weights, biases, activation), \
        (x, weights, biases)


def _mlp_bwd(activation, res, g):
    x, weights, biases = res
    if biases is None:
        def f(x_, w_):
            return mlp_reference(x_, w_, None, activation)
        _, vjp = jax.vjp(f, x, weights)
        dx, dw = vjp(g)
        return dx, dw, None
    def f(x_, w_, b_):
        return mlp_reference(x_, w_, b_, activation)
    _, vjp = jax.vjp(f, x, weights, biases)
    return vjp(g)


fused_mlp.defvjp(_mlp_fwd, _mlp_bwd)


class MLP:
    """flax module mirror of ``apex.mlp.MLP`` (`apex/mlp/mlp.py:8-79`):
    ``MLP([in, h1, h2, ...], bias=True, activation='relu')`` with params
    named ``weight_i`` / ``bias_i`` like the reference."""

    def __new__(cls, mlp_sizes: Sequence[int], bias: bool = True,
                activation: str = "relu"):
        import flax.linen as nn

        sizes = list(mlp_sizes)
        if len(sizes) < 2:
            raise ValueError("need at least [in, out] sizes")
        if activation not in _ACTS:
            raise ValueError(f"unknown activation {activation!r}")

        class _MLP(nn.Module):
            @nn.compact
            def __call__(self, x):
                weights, biases = [], ([] if bias else None)
                for i in range(len(sizes) - 1):
                    # reference init (`apex/mlp/mlp.py:63-72`): weights
                    # N(0, sqrt(2/(fan_in+fan_out))), biases
                    # N(0, sqrt(1/fan_out))
                    w_std = np.sqrt(2.0 / (sizes[i] + sizes[i + 1]))
                    w = self.param(
                        f"weight_{i}",
                        nn.initializers.normal(stddev=w_std),
                        (sizes[i], sizes[i + 1]), jnp.float32)
                    weights.append(w)
                    if bias:
                        b_std = np.sqrt(1.0 / sizes[i + 1])
                        b = self.param(
                            f"bias_{i}",
                            nn.initializers.normal(stddev=b_std),
                            (sizes[i + 1],), jnp.float32)
                        biases.append(b)
                return fused_mlp(x, tuple(weights),
                                 tuple(biases) if bias else None,
                                 activation)

        return _MLP()
