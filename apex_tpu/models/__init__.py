"""apex_tpu.models — the model families the reference's examples/configs
exercise (BASELINE.json): ResNet (imagenet example), DCGAN (multi-loss amp
example), BERT-style transformer (FusedLAMB config), RNN stacks
(`apex.RNN`), and a Kimi-Linear decoder (delta-rule linear attention, latent
attention, routed experts: one expert-parallel rank's share).
"""

from apex_tpu.models.resnet import (
    ResNet, ResNet18, ResNet50, ResNet101,
    BasicBlock, BottleneckBlock, RESNET50_FLOPS_PER_IMAGE,
)
from apex_tpu.models.transformer import (
    BertEncoder, BertLarge, TransformerLayer, MultiheadAttention,
    FusedLayerNormModule, mlm_loss,
)
from apex_tpu.models.dcgan import Generator, Discriminator
from apex_tpu.models.kimi_linear import (
    KimiLinear, KimiLinearDims, KimiDeltaAttention, LatentAttention,
    ExpertFFN, RMSNorm, kimi_linear_from_config, lm_loss,
)

__all__ = [
    "ResNet", "ResNet18", "ResNet50", "ResNet101",
    "BasicBlock", "BottleneckBlock", "RESNET50_FLOPS_PER_IMAGE",
    "BertEncoder", "BertLarge", "TransformerLayer", "MultiheadAttention",
    "FusedLayerNormModule", "mlm_loss",
    "Generator", "Discriminator",
    "KimiLinear", "KimiLinearDims", "KimiDeltaAttention", "LatentAttention",
    "ExpertFFN", "RMSNorm", "kimi_linear_from_config", "lm_loss",
]
