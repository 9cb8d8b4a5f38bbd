"""--arch <id> resolution."""
from __future__ import annotations

import importlib
from typing import List

from repro.models.common import ModelConfig

ARCHS = {
    "internvl2-2b": "repro.configs.internvl2_2b",
    "gemma2-9b": "repro.configs.gemma2_9b",
    "qwen2.5-14b": "repro.configs.qwen2_5_14b",
    "qwen1.5-110b": "repro.configs.qwen1_5_110b",
    "qwen2-0.5b": "repro.configs.qwen2_0_5b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "llama4-scout-17b-a16e": "repro.configs.llama4_scout_17b",
    "dbrx-132b": "repro.configs.dbrx_132b",
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
    "mamba2-370m": "repro.configs.mamba2_370m",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "gpt2-small": "repro.configs.gpt2",
}


def get_config(arch: str, reduced: bool = False) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(ARCHS)}")
    mod = importlib.import_module(ARCHS[arch])
    if arch == "gpt2-small":
        return mod.REDUCED if reduced else mod.GPT2_SMALL
    return mod.REDUCED if reduced else mod.CONFIG


def list_archs() -> List[str]:
    return [a for a in ARCHS if a != "gpt2-small"]
