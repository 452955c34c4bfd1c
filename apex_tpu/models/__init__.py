"""apex_tpu.models — the model families the reference's examples/configs
exercise (BASELINE.json): ResNet (imagenet example), DCGAN (multi-loss amp
example), BERT-style transformer (FusedLAMB config), RNN stacks
(`apex.RNN`), and five decoders over one shell (`decoder.py`: pre-norm
blocks, routed experts at one expert-parallel rank's share, next-token loss):
Kimi-Linear (delta-rule linear attention with a decay a channel, latent
attention), Qwen3-Next (gated DeltaNet with a decay a head and shared key
heads, gated grouped-query attention with partial rotary, softmax router),
LFM2-MoE (gated short convolutions, grouped-query attention with rotary over
the whole head behind per-head q/k norms, no shared expert, a tied head),
Laguna (sliding-window and global grouped-query attention, 3 : 1, with a
sigmoid gate a head and YaRN rotary on the global layers, softmax-routed
experts beside an ungated shared one) and DeepSeek-V3 (latent attention
with an interleaved rotary part in every layer, sigmoid-routed experts beside
a shared one); Kimi-Linear and DeepSeek-V3 share the latent attention of
``mla.py``.
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
from apex_tpu.models.decoder import (
    Block, Decoder, ExpertFFN, RMSNorm, SwiGLU, interleaved_rotary, lm_loss,
    partial_rotary, yarn_frequencies,
)
from apex_tpu.models.mla import LatentAttention
from apex_tpu.models.kimi_linear import (
    KimiLinear, KimiLinearDims, KimiDeltaAttention, kimi_linear_from_config,
)
from apex_tpu.models.qwen3_next import (
    Qwen3Next, Qwen3NextDims, GatedDeltaNet, GatedAttention,
    qwen3_next_from_config,
)
from apex_tpu.models.lfm2 import (
    Lfm2Moe, Lfm2Dims, GatedShortConv, GroupedQueryAttention,
    lfm2_moe_from_config,
)
from apex_tpu.models.laguna import (
    Laguna, LagunaDims, HeadGatedAttention, laguna_from_config,
)
from apex_tpu.models.deepseek_v3 import (
    DeepseekV3, DeepseekV3Dims, deepseek_v3_from_config,
)

__all__ = [
    "ResNet", "ResNet18", "ResNet50", "ResNet101",
    "BasicBlock", "BottleneckBlock", "RESNET50_FLOPS_PER_IMAGE",
    "BertEncoder", "BertLarge", "TransformerLayer", "MultiheadAttention",
    "FusedLayerNormModule", "mlm_loss",
    "Generator", "Discriminator",
    "Block", "Decoder", "ExpertFFN", "RMSNorm", "SwiGLU", "lm_loss",
    "KimiLinear", "KimiLinearDims", "KimiDeltaAttention", "LatentAttention",
    "kimi_linear_from_config",
    "Qwen3Next", "Qwen3NextDims", "GatedDeltaNet", "GatedAttention",
    "partial_rotary", "qwen3_next_from_config",
    "Lfm2Moe", "Lfm2Dims", "GatedShortConv", "GroupedQueryAttention",
    "lfm2_moe_from_config", "yarn_frequencies",
    "Laguna", "LagunaDims", "HeadGatedAttention", "laguna_from_config",
    "DeepseekV3", "DeepseekV3Dims", "deepseek_v3_from_config",
    "interleaved_rotary",
]
