"""Model zoo: the reference's benchmark/example workloads as TPU-first
flax models (SURVEY.md §6 / BASELINE.json north-star configs)."""
from .latent_moe import LATENT_MOE_CONFIGS, LatentMoEConfig, LatentMoELM
from .mnist import MnistCNN, MnistMLP
from .registry import REGISTRY, ModelSpec, get_model, list_models
from .resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from .transformer import (
    BERT_CONFIGS,
    GPT2_CONFIGS,
    SwitchMoE,
    TransformerConfig,
    TransformerEncoder,
    TransformerLM,
)
from .vit import VIT_CONFIGS, ViT, ViTConfig
