"""ResNet (v1.5) — the imagenet-example model family.

The reference's canonical benchmark drives torchvision ResNet-50 through
amp + apex DDP (`examples/imagenet/main_amp.py:130-180`). This is the
TPU-native equivalent: NHWC layout (TPU conv-native), flax modules, BN that
can sync over a mesh axis (``bn_axis_name`` ↔ ``--sync_bn``,
`main_amp.py:142-145`), and bottleneck blocks with the stride-on-3x3
placement (v1.5) that torchvision uses.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.parallel.sync_batchnorm import SyncBatchNorm
from apex_tpu.ops.bn_act import FusedBNAct


class _BN(nn.Module):
    """BatchNorm unit, optionally with fused residual-add and ReLU.

    ``dtype`` is the *activation* dtype (output in that dtype, stats and
    scale/offset always fp32) — keep_batchnorm_fp32 the TPU way: fp32
    parameters and statistics, half activations in and out, the cast
    fused into the normalize instead of materialized in HBM.

    ``fused=True`` (default) routes through :class:`FusedBNAct`, whose
    hand-written VJP saves only the conv output + per-channel stats and
    recomputes x̂/the ReLU mask — the traffic-minimal backward (the role
    of the reference's `nhwc_batch_norm_kernel.h` fused kernels). The
    unfused path keeps the round-2 module structure (flax BatchNorm /
    SyncBatchNorm submodule) as the autodiff oracle; note the param
    trees differ between the two (documented in docs/models.md).
    """
    features: int
    axis_name: Optional[str] = None
    momentum: float = 0.9
    epsilon: float = 1e-5
    init_scale: float = 1.0
    dtype: Optional[Any] = None
    relu: bool = False
    fused: bool = True

    @nn.compact
    def __call__(self, x, residual=None, train: bool = True):
        if self.fused:
            z = FusedBNAct(
                num_features=self.features, relu=self.relu,
                momentum=self.momentum, epsilon=self.epsilon,
                axis_name=self.axis_name, init_scale=self.init_scale,
                dtype=self.dtype)(x, residual, train=train)
            return z
        if self.dtype is not None:
            x = x.astype(self.dtype)
            if residual is not None:
                residual = residual.astype(self.dtype)
        if self.axis_name is not None:
            bn = SyncBatchNorm(
                num_features=self.features, momentum=1 - self.momentum,
                epsilon=self.epsilon, axis_name=self.axis_name,
                scale_init=nn.initializers.constant(self.init_scale))
            y = bn(x, use_running_average=not train)
        else:
            bn = nn.BatchNorm(
                use_running_average=not train, momentum=self.momentum,
                epsilon=self.epsilon, dtype=self.dtype,
                scale_init=nn.initializers.constant(self.init_scale))
            y = bn(x)
        if residual is not None:
            y = y + residual
        if self.relu:
            y = nn.relu(y)
        return y


class _StemConv(nn.Module):
    """The 7x7/2 stem conv, optionally via 2x2 space-to-depth.

    A C=3 input maps pathologically onto the MXU: 3 of 128 lanes carry
    data in the contracting dimension, so the stem's forward and weight
    gradient run far below roofline. The space-to-depth transform packs
    each 2x2 pixel cell into channels — (B, H, W, 3) → (B, H/2, W/2, 12)
    — and runs the arithmetically identical (4, 4, 12, K) stride-1 conv
    (the kernel zero-padded 7→8 taps so the stride-2 window aligns with
    whole cells). Parameters keep the canonical (7, 7, 3, K) shape, so
    checkpoints interchange with the plain stem; the kernel re-layout is
    37K params of in-graph reshuffling and gradients flow through it.
    """
    features: int
    space_to_depth: bool = True
    dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x):
        k = self.param("kernel", nn.initializers.lecun_normal(),
                       (7, 7, x.shape[-1], self.features), jnp.float32)
        # same dtype semantics as nn.Conv: explicit dtype wins, otherwise
        # promote input/param dtypes to a common compute dtype
        x, k = nn.dtypes.promote_dtype(x, k, dtype=self.dtype)
        b, h, w, c = x.shape
        if not self.space_to_depth or h % 2 or w % 2 or c != 3:
            return jax.lax.conv_general_dilated(
                x, k, window_strides=(2, 2), padding=[(3, 3), (3, 3)],
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # input: pack 2x2 cells into channels, sub-order (r, s, c)
        xs = x.reshape(b, h // 2, 2, w // 2, 2, c)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2,
                                                    4 * c)
        # kernel: zero-pad the window to 8x8 at the leading edge (the
        # stride-2 window [2i-3, 2i+3] becomes the cell-aligned
        # [2i-4, 2i+3]), then split taps p=2ρ+r into (cell ρ, sub r)
        k8 = jnp.pad(k, ((1, 0), (1, 0), (0, 0), (0, 0)))
        ks = k8.reshape(4, 2, 4, 2, c, self.features)
        ks = ks.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c,
                                                    self.features)
        # cells [i-2, i+1] feed output i → padding (2, 1), stride 1
        return jax.lax.conv_general_dilated(
            xs, ks, window_strides=(1, 1), padding=[(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))


# the stem is MXU-bound like any conv: under auto_cast(O1) it must cast
# to the half dtype with the rest of the whitelist (nn.Conv matches by
# isinstance; a custom module needs registering)
from apex_tpu.amp.lists import register_half_module as _reg_half
_reg_half(_StemConv)
del _reg_half


class BottleneckBlock(nn.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    bn_axis_name: Optional[str] = None
    dtype: Optional[Any] = None
    fused_bn: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        bn = partial(_BN, axis_name=self.bn_axis_name, dtype=self.dtype,
                     fused=self.fused_bn)
        residual = x
        y = conv(self.features, (1, 1))(x)
        y = bn(self.features, relu=True)(y, train=train)
        y = conv(self.features, (3, 3), self.strides)(y)
        y = bn(self.features, relu=True)(y, train=train)
        # module creation order is load-bearing: flax's auto-names
        # (Conv_2 = final 1x1, Conv_3 = projection) are the checkpoint
        # layout
        y = conv(self.features * 4, (1, 1))(y)
        if residual.shape[-1] != self.features * 4 \
                or self.strides != (1, 1):
            residual = conv(self.features * 4, (1, 1), self.strides)(x)
            residual = bn(self.features * 4)(residual, train=train)
        # zero-init the last BN scale: standard ResNet recipe (identity
        # residual at init); the residual add + relu fuse into this unit
        return bn(self.features * 4, init_scale=0.0, relu=True)(
            y, residual, train=train)


class BasicBlock(nn.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    bn_axis_name: Optional[str] = None
    dtype: Optional[Any] = None
    fused_bn: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        bn = partial(_BN, axis_name=self.bn_axis_name, dtype=self.dtype,
                     fused=self.fused_bn)
        residual = x
        y = conv(self.features, (3, 3), self.strides)(x)
        y = bn(self.features, relu=True)(y, train=train)
        y = conv(self.features, (3, 3))(y)
        if residual.shape[-1] != self.features or self.strides != (1, 1):
            residual = conv(self.features, (1, 1), self.strides)(x)
            residual = bn(self.features)(residual, train=train)
        return bn(self.features, init_scale=0.0, relu=True)(
            y, residual, train=train)


class ResNet(nn.Module):
    """NHWC ResNet; input (N, H, W, 3)."""
    stage_sizes: Sequence[int]
    block: Any = BottleneckBlock
    num_classes: int = 1000
    width: int = 64
    bn_axis_name: Optional[str] = None
    #: activation/compute dtype — set to ``policy.compute_dtype`` for mixed
    #: precision (the O2 model-cast; params stay ``param_dtype`` fp32 and
    #: are cast per-op by flax, masters live in AmpState).
    dtype: Optional[Any] = None
    #: run the stem via 2x2 space-to-depth (MXU-friendly C=12 layout);
    #: automatically falls back to the plain 7x7/2 conv for odd sizes
    space_to_depth: bool = True
    #: minimal-residual fused BN(+add)(+relu) backward (see ops/bn_act.py);
    #: False = plain flax BatchNorm autodiff (the numeric oracle)
    fused_bn: bool = True

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.dtype is not None:
            x = x.astype(self.dtype)  # patched-forward input cast
        y = _StemConv(self.width, space_to_depth=self.space_to_depth,
                      dtype=self.dtype, name="stem_conv")(x)
        y = _BN(self.width, self.bn_axis_name, dtype=self.dtype,
                relu=True, fused=self.fused_bn)(y, train=train)
        y = nn.max_pool(y, (3, 3), (2, 2), padding=[(1, 1), (1, 1)])
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                y = self.block(self.width * 2 ** i, strides,
                               self.bn_axis_name, self.dtype,
                               self.fused_bn)(y, train)
        y = jnp.mean(y, axis=(1, 2))
        return nn.Dense(self.num_classes, dtype=self.dtype)(y)


def ResNet18(**kw):
    return ResNet(stage_sizes=[2, 2, 2, 2], block=BasicBlock, **kw)


def ResNet50(**kw):
    return ResNet(stage_sizes=[3, 4, 6, 3], block=BottleneckBlock, **kw)


def ResNet101(**kw):
    return ResNet(stage_sizes=[3, 4, 23, 3], block=BottleneckBlock, **kw)


#: fwd-pass MACs per 224x224 image — used by bench MFU accounting.
RESNET50_FLOPS_PER_IMAGE = 2 * 4.09e9  # 4.09 GMACs fwd (torchvision count)
