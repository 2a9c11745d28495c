"""Architecture registry: --arch <id> resolution for launchers and tests."""
from __future__ import annotations

from repro.common.types import ModelCfg, SHAPES, ShapeSpec  # re-export

from repro.configs import (
    bert,
    deepseek_moe_16b,
    deepseek_v2_lite,
    gemma2_27b,
    internvl2_76b,
    qwen3_0_6b,
    qwen3_moe_235b_a22b,
    recurrentgemma_2b,
    rwkv6_1_6b,
    starcoder2_3b,
    starcoder2_7b,
    whisper_tiny,
)

# the 10 assigned architectures, and those added since for the benchmark
ASSIGNED = {
    "deepseek-moe-16b": deepseek_moe_16b,
    "deepseek-v2-lite": deepseek_v2_lite,
    "qwen3-moe-235b-a22b": qwen3_moe_235b_a22b,
    "recurrentgemma-2b": recurrentgemma_2b,
    "whisper-tiny": whisper_tiny,
    "rwkv6-1.6b": rwkv6_1_6b,
    "starcoder2-7b": starcoder2_7b,
    "starcoder2-3b": starcoder2_3b,
    "qwen3-0.6b": qwen3_0_6b,
    "gemma2-27b": gemma2_27b,
    "internvl2-76b": internvl2_76b,
}

# the paper's own PLMs (encoder classifiers for the GLUE-style benchmarks)
PAPER = {
    "bert-base": bert.bert_base,
    "bert-large": bert.bert_large,
    "roberta-base": bert.roberta_base,
    "roberta-large": bert.roberta_large,
    "bert-small": bert.bert_small,
    "bert-tiny": bert.bert_tiny,
}


def list_archs():
    return sorted(ASSIGNED)


def get(name: str) -> ModelCfg:
    if name in ASSIGNED:
        return ASSIGNED[name].config()
    if name in PAPER:
        return PAPER[name]()
    raise KeyError(f"unknown arch {name!r}; known: {list_archs() + sorted(PAPER)}")


def get_smoke(name: str) -> ModelCfg:
    if name in ASSIGNED:
        return ASSIGNED[name].smoke()
    if name in PAPER:
        return bert.smoke()
    raise KeyError(name)
