"""--arch <id> registry over the 10 assigned architectures (+ paper-native
micro workloads used by the examples)."""
from __future__ import annotations

from typing import Dict

from repro.configs.base import ModelConfig

from repro.configs.whisper_tiny import CONFIG as WHISPER_TINY
from repro.configs.qwen2_vl_7b import CONFIG as QWEN2_VL_7B
from repro.configs.phi3_5_moe import CONFIG as PHI35_MOE
from repro.configs.granite_moe_1b import CONFIG as GRANITE_MOE_1B
from repro.configs.jamba_v0_1 import CONFIG as JAMBA_V01
from repro.configs.qwen2_5_32b import CONFIG as QWEN25_32B
from repro.configs.qwen1_5_32b import CONFIG as QWEN15_32B
from repro.configs.gemma2_2b import CONFIG as GEMMA2_2B
from repro.configs.qwen3_1_7b import CONFIG as QWEN3_17B
from repro.configs.xlstm_1_3b import CONFIG as XLSTM_13B
from repro.configs.lfm2_8b_a1b import CONFIG as LFM2_8B_A1B
from repro.configs.micro_lm import CONFIG as MICRO_LM, CONFIG_100M as MICRO_LM_100M

ARCHS: Dict[str, ModelConfig] = {
    "whisper-tiny": WHISPER_TINY,
    "qwen2-vl-7b": QWEN2_VL_7B,
    "phi3.5-moe-42b-a6.6b": PHI35_MOE,
    "granite-moe-1b-a400m": GRANITE_MOE_1B,
    "jamba-v0.1-52b": JAMBA_V01,
    "qwen2.5-32b": QWEN25_32B,
    "qwen1.5-32b": QWEN15_32B,
    "gemma2-2b": GEMMA2_2B,
    "qwen3-1.7b": QWEN3_17B,
    "xlstm-1.3b": XLSTM_13B,
    # supported beyond the assigned ten (a benchmark configuration)
    "lfm2-8b-a1b": LFM2_8B_A1B,
    # paper-native single-node workloads (examples / simulator jobs)
    "micro-lm": MICRO_LM,
    "micro-lm-100m": MICRO_LM_100M,
}

ASSIGNED = tuple(k for k in ARCHS
                 if not k.startswith("micro-lm") and k != "lfm2-8b-a1b")


def get_config(arch: str) -> ModelConfig:
    if arch not in ARCHS:
        raise KeyError(f"unknown arch {arch!r}; available: {sorted(ARCHS)}")
    return ARCHS[arch]
