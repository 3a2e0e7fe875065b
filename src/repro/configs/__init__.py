"""Architecture registry: ``get_config(arch_id)`` / ``list_archs()``."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401 (re-export)
    MLAConfig, MoEConfig, ModelConfig, SSMConfig, EncDecConfig, VLMConfig,
    YarnConfig, ShapeConfig, SHAPES, SHAPES_BY_NAME, reduced,
    shape_applicable)

_ARCH_MODULES: Dict[str, str] = {
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "zamba2-1.2b": "repro.configs.zamba2_1p2b",
    "qwen1.5-0.5b": "repro.configs.qwen1p5_0p5b",
    "granite-3-2b": "repro.configs.granite_3_2b",
    "codeqwen1.5-7b": "repro.configs.codeqwen1p5_7b",
    "mistral-large-123b": "repro.configs.mistral_large_123b",
    "llama-3.2-vision-90b": "repro.configs.llama_3p2_vision_90b",
    "xlstm-350m": "repro.configs.xlstm_350m",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
}


def list_archs() -> List[str]:
    return list(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG


def get_family(name: str) -> Dict[str, ModelConfig]:
    """RAG model families, role -> config: 'qwen3' (the paper's Fig. 5),
    'bge' (Fig. 6), or 'deepseek-v2-lite' (Qwen3's embedder and reranker,
    with one DeepSeek-V2-Lite config object as both search and chat)."""
    if name == "qwen3":
        return importlib.import_module("repro.configs.qwen3_family").FAMILY
    if name == "bge":
        return importlib.import_module("repro.configs.bge_family").FAMILY
    if name == "deepseek-v2-lite":
        qwen3 = importlib.import_module("repro.configs.qwen3_family").FAMILY
        lite = importlib.import_module("repro.configs.deepseek_v2_lite")
        return {"embed": qwen3["embed"], "rerank": qwen3["rerank"],
                "search": lite.CONFIG, "chat": lite.CONFIG}
    raise KeyError(f"unknown family {name!r}; known: qwen3, bge, "
                   f"deepseek-v2-lite")
