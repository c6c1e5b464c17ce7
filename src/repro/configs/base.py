"""Architecture & run configuration dataclasses.

Every assigned architecture is expressed as an :class:`ArchConfig`. The config is a
plain frozen dataclass (hashable, usable as a jit static argument) and intentionally
carries *everything* a model needs — there is no hidden global state.

Families
--------
``dense``    decoder-only transformer (GQA attention + SwiGLU/GeLU MLP)
``moe``      dense skeleton with the MLP replaced by a token-choice MoE
``ssm``      attention-free Mamba-2 (SSD) stack
``hybrid``   interleaved attention/Mamba-2 blocks (+ optional MoE), e.g. Jamba
``encdec``   encoder-decoder transformer (Whisper); frontend stubbed
``vlm``      decoder-only backbone with M-RoPE (Qwen2-VL); vision frontend stubbed
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Token-choice MoE sub-config."""

    num_experts: int
    top_k: int
    num_shared_experts: int = 0
    expert_ff: int = 0              # per-expert intermediate dim (0 -> use arch d_ff)
    capacity_factor: float = 1.25
    # every `every` layers one MoE layer; 1 == every layer is MoE
    every: int = 1
    # index offset of the first MoE layer
    first: int = 0
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01
    # top-k router weights renormalised to sum to 1 (False: the raw softmax
    # probabilities over all experts, as DeepSeekMoE weights them)
    norm_topk: bool = True
    # serving computes every assignment (no capacity, nothing dropped);
    # the training forward keeps capacity dispatch at ``capacity_factor``
    dropless: bool = False
    # routed experts whose weights this layer holds: the first
    # ``held_count`` (0 = all ``num_experts``). The router still scores all
    # of them; assignments to experts held elsewhere add nothing here.
    held_count: int = 0

    @property
    def num_held(self) -> int:
        return self.held_count or self.num_experts


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 (SSD, arXiv:2405.21060) sub-config."""

    state_dim: int = 128            # N — SSM state size per head
    head_dim: int = 64              # P — channels per SSD head
    expand: int = 2                 # inner dim = expand * d_model
    chunk: int = 256                # SSD chunk length
    conv_width: int = 4             # depthwise causal conv width
    ngroups: int = 1                # B/C groups (GVA-style)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """A complete, paper-faithful architecture description."""

    name: str
    family: str                     # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int
    d_model: int
    num_heads: int                  # query heads (0 for attention-free)
    num_kv_heads: int               # GQA kv heads
    d_ff: int                       # MLP intermediate (per expert for fine-grained MoE)
    vocab_size: int
    head_dim: int = 0               # 0 -> d_model // num_heads
    # --- nonlinearity / block style ------------------------------------------------
    mlp: str = "swiglu"             # swiglu | gelu
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    norm_eps: float = 1e-5          # added to the variance in every norm
    pos_emb: str = "rope"           # rope | mrope | learned | sinusoidal | none
    rope_theta: float = 10_000.0
    use_bias: bool = False
    tie_embeddings: bool = False
    # --- family payloads -------------------------------------------------------
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid: period and which index within the period is attention (Jamba: 1 attn per
    # 8 layers, at index 4 of each period by convention)
    hybrid_period: int = 0
    hybrid_attn_index: int = 0
    # encoder (encdec only)
    enc_layers: int = 0
    enc_seq_len: int = 0            # encoder frames per example (whisper: 1500)
    # frontends (audio/vision) are stubs: inputs arrive as precomputed embeddings
    frontend: str = "none"          # none | audio_stub | vision_stub
    # --- numerics ----------------------------------------------------------------
    dtype: str = "bfloat16"         # compute/activation dtype
    param_dtype: str = "float32"    # master parameter dtype
    # --- attention impl ------------------------------------------------------------
    attn_impl: str = "chunked"      # naive | chunked | flash
    attn_chunk: int = 1024          # kv-block for chunked/flash attention
    # sliding-window attention (0 = full); used beyond-paper for long-context cells
    window: int = 0
    # --- block style variants --------------------------------------------------
    post_norm: bool = False         # BERT-style post-LN blocks
    bidirectional: bool = False     # encoder-only attention (BERT); no decode step
    mlm_transform: bool = False     # BERT MLM output head (dense+gelu+LN)
    max_position: int = 512         # learned-position table size
    # --- training ------------------------------------------------------------------
    remat: bool = True
    scan_layers: bool = True
    logit_softcap: float = 0.0

    # ------------------------------------------------------------------ helpers ---
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads == 0:
            return 0
        return self.d_model // self.num_heads

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.resolved_head_dim

    def is_attention_layer(self, layer_idx: int) -> bool:
        """For hybrid stacks: does layer ``layer_idx`` use attention?"""
        if self.family in ("dense", "moe", "encdec", "vlm"):
            return True
        if self.family == "ssm":
            return False
        assert self.hybrid_period > 0
        return layer_idx % self.hybrid_period == self.hybrid_attn_index

    def is_moe_layer(self, layer_idx: int) -> bool:
        if self.moe is None:
            return False
        m = self.moe
        return layer_idx >= m.first and (layer_idx - m.first) % m.every == 0

    # -- parameter counting (used for roofline MODEL_FLOPS = 6*N*D) ---------------
    def param_count(self, active_only: bool = False) -> int:
        """Closed-form parameter count (embedding included once)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        hd = self.resolved_head_dim
        total = v * d                                     # embedding
        if not self.tie_embeddings:
            total += v * d                                # lm head
        bias = 1 if self.use_bias else 0

        def attn_params() -> int:
            qp = d * self.q_dim + bias * self.q_dim
            kp = d * self.kv_dim + bias * self.kv_dim
            vp = d * self.kv_dim + bias * self.kv_dim
            op = self.q_dim * d + bias * d
            return qp + kp + vp + op

        def mlp_params(inner: int) -> int:
            if self.mlp == "swiglu":
                return 3 * d * inner + bias * (2 * inner + d)
            return 2 * d * inner + bias * (inner + d)

        def moe_params(active: bool) -> int:
            m = self.moe
            eff = m.expert_ff or ff
            router = d * m.num_experts
            shared = m.num_shared_experts * mlp_params(eff)
            routed = (m.top_k if active else m.num_experts) * mlp_params(eff)
            return router + shared + routed

        def ssm_params() -> int:
            s = self.ssm
            inner = s.expand * d
            nheads = inner // s.head_dim
            in_proj = d * (2 * inner + 2 * s.ngroups * s.state_dim + nheads)
            conv = s.conv_width * (inner + 2 * s.ngroups * s.state_dim)
            out_proj = inner * d
            extra = 3 * nheads + inner                     # A, D, dt_bias, gate norm
            return in_proj + conv + out_proj + extra

        for layer in range(self.num_layers):
            total += 2 * d                                 # two norms per block
            if self.is_attention_layer(layer):
                total += attn_params()
            else:
                total += ssm_params()
            if self.is_moe_layer(layer):
                total += moe_params(active_only)
            elif self.family == "ssm":
                pass                                       # mamba blocks have no MLP
            else:
                total += mlp_params(ff)
        if self.family == "encdec":
            # encoder layers: self-attn + mlp; decoder layers already counted above
            for _ in range(self.enc_layers):
                total += attn_params() + mlp_params(ff) + 2 * d
            # decoder cross-attention
            total += self.num_layers * (attn_params() + d)
        return total


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell from the assignment grid."""

    name: str
    seq_len: int
    global_batch: int
    kind: str                       # train | prefill | decode
    # number of gradient-accumulation microbatches for the train kind (paper §4.2)
    microbatches: int = 1


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Everything the step builder needs besides the architecture itself."""

    arch: "ArchConfig"
    shape: "ShapeConfig"
    optimizer: str = "lamb"         # lamb | adamw | sgd
    learning_rate: float = 1e-3
    weight_decay: float = 0.01
    zero1: bool = True              # shard optimizer states over the data axis
    fuse_qkv: bool = True           # paper Fig 14/15 GEMM fusion
    fused_optimizer_kernel: bool = False   # route LAMB through the Pallas kernel
    # bf16 model params + fp32 master copies in the optimizer (paper §3.2.1 MP);
    # False = everything fp32 (the paper's FP32 baseline)
    master_weights: bool = True
    opt_state_dtype: str = "float32"       # bf16 = quantized m/v (beyond-paper)
    grad_clip: float = 1.0
    seed: int = 0
    # logical-axis overrides: tuple of (logical_name, mesh_axis|None)
    sharding_overrides: Tuple[Tuple[str, Optional[str]], ...] = ()

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)
