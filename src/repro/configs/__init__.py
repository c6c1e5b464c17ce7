"""Config registry: ``get_config("<arch-id>")`` and reduced smoke configs."""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from .base import ArchConfig, MoEConfig, RunConfig, ShapeConfig, SSMConfig
from .shapes import (DECODE_32K, LONG_500K, PREFILL_32K, SHAPES, TRAIN_4K,
                     cell_supported, input_specs)

from . import (bert_large, command_r_35b, deepseek_moe_16b, internlm2_1p8b,
               jamba_v0p1_52b, llama3p2_3b, llama4_maverick_400b,
               mamba2_1p3b, mistral_large_123b, qwen2_vl_2b, whisper_base)

_MODULES = (
    mistral_large_123b, command_r_35b, internlm2_1p8b, llama3p2_3b,
    deepseek_moe_16b, llama4_maverick_400b, whisper_base, mamba2_1p3b,
    jamba_v0p1_52b, qwen2_vl_2b, bert_large,
)

REGISTRY: Dict[str, ArchConfig] = {m.CONFIG.name: m.CONFIG for m in _MODULES}
# one chip's share of a deployment, beside the whole model it is cut from
REGISTRY[deepseek_moe_16b.EP4.name] = deepseek_moe_16b.EP4

# the 10 assigned archs (bert-large is the paper's own model, listed separately)
ASSIGNED: List[str] = [m.CONFIG.name for m in _MODULES[:-1]]


def get_config(name: str) -> ArchConfig:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}") from None


def list_archs() -> List[str]:
    return list(REGISTRY)


def smoke_config(name: str) -> ArchConfig:
    """A reduced same-family config: tiny widths/layers, CPU-runnable in seconds."""
    full = get_config(name)
    kw = dict(
        name=full.name + "-smoke",
        num_layers=max(2, full.hybrid_period) if full.family == "hybrid" else 2,
        d_model=128,
        d_ff=0 if full.family == "ssm" else 256,
        vocab_size=512,
        head_dim=32,
        rope_theta=full.rope_theta,
        attn_chunk=64,
    )
    if full.num_heads:
        kw["num_heads"] = 4
        kw["num_kv_heads"] = min(4, max(1, full.num_kv_heads // 4)) or 1
    else:
        kw["num_heads"] = 0
        kw["num_kv_heads"] = 0
    if full.moe is not None:
        kw["moe"] = dataclasses.replace(
            full.moe, num_experts=4,
            top_k=min(2, full.moe.top_k),
            expert_ff=256 if full.moe.expert_ff else 0,
            held_count=2 if full.moe.held_count else 0)
    if full.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            full.ssm, state_dim=16, head_dim=16, chunk=16)
    if full.family == "encdec":
        kw["enc_layers"] = 2
        kw["enc_seq_len"] = 16
    return dataclasses.replace(full, **kw)


__all__ = [
    "ArchConfig", "MoEConfig", "SSMConfig", "RunConfig", "ShapeConfig",
    "REGISTRY", "ASSIGNED", "SHAPES",
    "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "get_config", "list_archs", "smoke_config", "cell_supported", "input_specs",
]
