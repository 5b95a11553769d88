"""Model registry: name → (flax module, synthetic-batch factory).

Mirrors how the reference's benchmark scripts look models up by name
(`examples/pytorch_synthetic_benchmark.py --model resnet50` resolves
through `torchvision.models.__dict__`). Synthetic batches match the
benchmark data shapes (224x224x3 images; token ids for LMs).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import numpy as np

from .latent_moe import LATENT_MOE_CONFIGS, LatentMoELM
from .linear_moe import LINEAR_MOE_CONFIGS, LinearMoELM
from .mnist import MnistCNN, MnistMLP
from .resnet import RESNET_CONFIGS
from .transformer import (
    BERT_CONFIGS,
    GPT2_CONFIGS,
    TransformerConfig,
    TransformerEncoder,
    TransformerLM,
)
from .vit import VIT_CONFIGS, ViT
from .window_moe import WINDOW_MOE_CONFIGS, WindowMoELM


@dataclasses.dataclass
class ModelSpec:
    name: str
    make_model: Callable[..., Any]
    make_batch: Callable[[int], Any]   # batch_size -> example inputs tuple
    kind: str                          # "image" | "lm" | "encoder"


def _image_batch(hw: int, channels: int = 3):
    def make(batch_size: int, seed: int = 0):
        rng = np.random.RandomState(seed)
        return (rng.rand(batch_size, hw, hw, channels).astype(np.float32),)

    return make


def _token_batch(seq_len: int, vocab: int):
    def make(batch_size: int, seed: int = 0, seq_len: int = seq_len):
        rng = np.random.RandomState(seed)
        return (rng.randint(0, vocab, size=(batch_size, seq_len),
                            dtype=np.int32),)

    return make


def _registry() -> Dict[str, ModelSpec]:
    reg: Dict[str, ModelSpec] = {}
    reg["mnist-mlp"] = ModelSpec("mnist-mlp", MnistMLP, _image_batch(28, 1),
                                 "image")
    reg["mnist-cnn"] = ModelSpec("mnist-cnn", MnistCNN, _image_batch(28, 1),
                                 "image")
    for name, ctor in RESNET_CONFIGS.items():
        reg[name] = ModelSpec(name, ctor, _image_batch(224), "image")
    for name, cfg in GPT2_CONFIGS.items():
        reg[name] = ModelSpec(
            name,
            (lambda c: (lambda **kw: TransformerLM(
                dataclasses.replace(c, **kw) if kw else c)))(cfg),
            _token_batch(min(cfg.max_len, 512), cfg.vocab_size),
            "lm",
        )
    for name, cfg in BERT_CONFIGS.items():
        reg[name] = ModelSpec(
            name,
            (lambda c: (lambda **kw: TransformerEncoder(
                dataclasses.replace(c, **kw) if kw else c)))(cfg),
            _token_batch(min(cfg.max_len, 128), cfg.vocab_size),
            "encoder",
        )
    for name, cfg in LATENT_MOE_CONFIGS.items():
        reg[name] = ModelSpec(
            name,
            (lambda c: (lambda **kw: LatentMoELM(
                dataclasses.replace(c, **kw) if kw else c)))(cfg),
            _token_batch(512, cfg.vocab_size), "lm",
        )
    for name, cfg in WINDOW_MOE_CONFIGS.items():
        reg[name] = ModelSpec(
            name,
            (lambda c: (lambda **kw: WindowMoELM(
                dataclasses.replace(c, **kw) if kw else c)))(cfg),
            _token_batch(512, cfg.vocab_size), "lm",
        )
    for name, cfg in LINEAR_MOE_CONFIGS.items():
        reg[name] = ModelSpec(
            name,
            (lambda c: (lambda **kw: LinearMoELM(
                dataclasses.replace(c, **kw) if kw else c)))(cfg),
            _token_batch(512, cfg.vocab_size), "lm",
        )
    for name, cfg in VIT_CONFIGS.items():
        reg[name] = ModelSpec(
            name,
            (lambda c: (lambda **kw: ViT(
                dataclasses.replace(c, **kw) if kw else c)))(cfg),
            _image_batch(cfg.image_size), "image",
        )
    return reg


REGISTRY = _registry()


def get_model(name: str) -> ModelSpec:
    if name not in REGISTRY:
        raise KeyError(
            f"unknown model {name!r}; available: {sorted(REGISTRY)}"
        )
    return REGISTRY[name]


def list_models():
    return sorted(REGISTRY)
