"""Architecture registry: ``--arch <id>`` ids -> (full config, smoke config).

The ten assigned architectures, the hybrid Mamba-2 / MoE Nemotron 3 Nano,
plus the paper's own ViT evaluation model
(the latter lives in :mod:`repro.models.vit` with its own config type and
is exposed here for the benchmarks, not for the LM dry-run matrix).
"""
from __future__ import annotations

from repro.configs import (gemma2_2b, hubert_xlarge, llama3_8b, mamba2_130m,
                           mixtral_8x7b, nemotron_3_nano, phi3_5_moe,
                           phi3_vision_4_2b, qwen1_5_32b, recurrentgemma_9b,
                           starcoder2_3b)
from repro.configs.base import ModelConfig

_MODULES = {
    "llama3-8b": llama3_8b,
    "gemma2-2b": gemma2_2b,
    "starcoder2-3b": starcoder2_3b,
    "qwen1.5-32b": qwen1_5_32b,
    "mixtral-8x7b": mixtral_8x7b,
    "phi3.5-moe-42b-a6.6b": phi3_5_moe,
    "recurrentgemma-9b": recurrentgemma_9b,
    "hubert-xlarge": hubert_xlarge,
    "phi-3-vision-4.2b": phi3_vision_4_2b,
    "mamba2-130m": mamba2_130m,
    "nemotron-3-nano-30b-a3b": nemotron_3_nano,
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    return _MODULES[arch].CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _MODULES[arch].smoke()


def all_configs() -> dict[str, ModelConfig]:
    return {name: mod.CONFIG for name, mod in _MODULES.items()}
