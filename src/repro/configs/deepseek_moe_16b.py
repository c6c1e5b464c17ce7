"""deepseek-moe-16b [moe] — arXiv:2401.06066.

28L d_model=2048 16H (MHA, kv=16) vocab=102400. Layer 0 is dense (SwiGLU
10944, ``first_k_dense_replace`` 1); layers 1-27 are fine-grained MoE: a
float32 softmax router over 64 routed experts, the top 6 kept with their raw
probabilities as weights (``norm_topk_prob`` false), each expert a SwiGLU of
width 1408, plus 2 shared experts (one always-on SwiGLU of width 2816).
Inference is dropless: every token reaches all six of its experts. The
training forward keeps capacity dispatch (``capacity_factor`` 1.25), so
it computes about top-6 x 1.25 expert passes a token, not all 64.

``EP4`` is one chip's share of a four-chip expert-parallel deployment: it
holds routed experts 0-15 of every MoE layer (the router still scores all
64), both shared experts, all heads and the whole vocabulary.

Paper-characterization relevance: total params (~16B) >> active params (~2.8B), so the
LAMB optimizer reads/writes 4x *total* model size while step FLOPs track *active*
params — Takeaway 8 (memory-intensity of the optimizer) is amplified ~6x vs dense.
"""
import dataclasses

from .base import ArchConfig, MoEConfig

CONFIG = ArchConfig(
    name="deepseek-moe-16b",
    family="moe",
    num_layers=28,
    d_model=2_048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=10_944,
    vocab_size=102_400,
    head_dim=128,
    mlp="swiglu",
    norm="rmsnorm",
    norm_eps=1e-6,
    pos_emb="rope",
    rope_theta=10_000.0,
    use_bias=False,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        num_shared_experts=2,
        expert_ff=1_408,
        every=1,
        first=1,
        norm_topk=False,
        dropless=True,
    ),
)

EP4 = dataclasses.replace(
    CONFIG, name="deepseek-moe-16b-ep4",
    moe=dataclasses.replace(CONFIG.moe, held_count=16))
