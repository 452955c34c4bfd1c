"""apex_tpu.ops — fused Pallas kernels (SURVEY.md §2.10).

Every native module of the reference maps here: the `amp_C` multi-tensor
family (multi_tensor.py), the optimizer functors (optim_kernels.py), fused
LayerNorm / MLP / softmax-CE / NHWC BatchNorm / attention (their own
modules). All kernels run compiled on TPU and in interpret mode elsewhere.
"""

from apex_tpu.ops.multi_tensor import (
    multi_tensor_axpby,
    multi_tensor_l2norm,
    multi_tensor_maxnorm,
    multi_tensor_scale,
    per_tensor_l2norm,
)
from apex_tpu.ops import optim_kernels
from apex_tpu.ops.layer_norm import (
    FusedLayerNorm,
    fused_layer_norm,
    fused_layer_norm_affine,
    layer_norm_reference,
)
from apex_tpu.ops.mlp import MLP, fused_mlp, mlp_reference
from apex_tpu.ops.xentropy import (
    softmax_cross_entropy_loss,
    softmax_cross_entropy_reference,
)
from apex_tpu.ops.group_bn import BatchNorm2d_NHWC, bn_group_spec
from apex_tpu.ops.bn_act import (
    FusedBNAct,
    bn_act_reference,
    bn_act_train,
    bn_add_act_train,
)
from apex_tpu.ops.attention import (
    flash_attention,
    attention_reference,
    mask_softmax_dropout,
)
from apex_tpu.ops.multihead_attn import SelfMultiheadAttn, EncdecMultiheadAttn
from apex_tpu.ops.delta_rule import (
    gated_delta_rule,
    gated_delta_rule_reference,
)
from apex_tpu.ops import grouped_matmul  # the module: its two functions
from apex_tpu.ops import moe
from apex_tpu.ops import short_conv     # the module: short_conv.short_conv
from apex_tpu.ops import autotune
from apex_tpu.ops._dispatch import KEPT_ATTN, KEPT_KDA, KEPT_NAMES

__all__ = [
    "autotune",
    "multi_tensor_axpby", "multi_tensor_l2norm", "multi_tensor_maxnorm",
    "multi_tensor_scale", "per_tensor_l2norm", "optim_kernels",
    "FusedLayerNorm", "fused_layer_norm", "fused_layer_norm_affine",
    "layer_norm_reference", "MLP", "fused_mlp", "mlp_reference",
    "softmax_cross_entropy_loss", "softmax_cross_entropy_reference",
    "BatchNorm2d_NHWC", "bn_group_spec",
    "FusedBNAct", "bn_act_reference", "bn_act_train", "bn_add_act_train",
    "flash_attention", "attention_reference", "mask_softmax_dropout",
    "SelfMultiheadAttn", "EncdecMultiheadAttn",
    "gated_delta_rule", "gated_delta_rule_reference", "moe", "short_conv",
    "grouped_matmul",
    "KEPT_ATTN", "KEPT_KDA", "KEPT_NAMES",
]
