"""Model zoo matching the reference's example models (SURVEY.md section 2.8):
MNIST MLP, ImageNet family (AlexNet / GoogLeNet / ResNet-50), seq2seq LSTM —
plus the Transformer LM the benchmark configs add (BASELINE.json) and the
ViT-S/16 encoder family (beyond the reference: the MXU-natural ImageNet
model, built on the LM's TransformerBlock with ``causal=False``)."""

from chainermn_tpu.models.mlp import MLP
from chainermn_tpu.models.vit import VisionTransformer
from chainermn_tpu.models.imagenet import AlexNet, GoogLeNet
from chainermn_tpu.models.seq2seq import (
    Seq2Seq,
    beam_search_decode,
    greedy_decode,
    seq2seq_loss,
)
from chainermn_tpu.models.transformer import (
    MODEL_CONFIGS,
    ROUTER_STATE,
    Architecture,
    TransformerLM,
    head_table,
    block_diffusion_noise,
    block_diffusion_rows,
    diffusion_noise_key,
    diffusion_noise_state,
    lm_from_config,
    lm_loss_block_diffusion,
    lm_loss_looped,
    lm_loss_moe,
    mlm_corrupt,
    mlm_loss,
    beam_search,
    generate,
    init_cache,
    lm_loss,
    lm_loss_fused,
)
from chainermn_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from chainermn_tpu.models.detection import (
    TinyDetector,
    TwoStageDetector,
    detection_loss,
    two_stage_loss,
)

__all__ = [
    "VisionTransformer",
    "MLP",
    "AlexNet",
    "GoogLeNet",
    "Seq2Seq",
    "beam_search_decode",
    "greedy_decode",
    "seq2seq_loss",
    "TransformerLM",
    "Architecture",
    "MODEL_CONFIGS",
    "ROUTER_STATE",
    "lm_from_config",
    "head_table",
    "lm_loss_moe",
    "lm_loss_looped",
    "lm_loss_block_diffusion",
    "block_diffusion_noise",
    "block_diffusion_rows",
    "diffusion_noise_state",
    "diffusion_noise_key",
    "mlm_corrupt",
    "mlm_loss",
    "lm_loss",
    "lm_loss_fused",
    "generate",
    "beam_search",
    "init_cache",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "TinyDetector",
    "TwoStageDetector",
    "detection_loss",
    "two_stage_loss",
]
