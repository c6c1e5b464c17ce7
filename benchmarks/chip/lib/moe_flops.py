"""Operations and bytes of a MoE configuration's decode work, counted by
hand from the layer equations and from the engine's expert counters
(``expert_rows``: token-to-held-expert assignments; ``experts_touched``:
held experts with at least one; both summed over a step's MoE layers).
Never read from the program or from XLA.

``cfg`` is a MoE configuration file's dict (``configs/<name>.json``): the
first ``first_k_dense_replace`` layers a SwiGLU MLP of width
``intermediate_size``, every later one a router over all the published
experts (``held_experts["of"]``), the ``n_routed_experts`` of them held
here, SwiGLU of width ``moe_intermediate_size``, and ``n_shared_experts``
always on.
"""
from __future__ import annotations

from typing import Iterable

from . import flops

BF16 = flops.BF16
F32 = 4


def moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_params(cfg: dict) -> int:
    """Weights of one routed expert: w1, w3 and w2."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_kernel(cfg: dict, rows: int, touched: int) -> tuple:
    """(FLOPs, bytes) of the grouped expert kernel over ``rows``
    assignments to ``touched`` held experts: 2 x 3 d f a row; each touched
    expert's weights read once, each row read in bf16 and written in
    float32."""
    d = cfg["hidden_size"]
    return (2.0 * expert_params(cfg) * rows,
            touched * expert_params(cfg) * BF16 + rows * d * (BF16 + F32))


def dense_params_per_token(cfg: dict) -> int:
    """Weights every token is multiplied by, outside the routed experts:
    attention in every layer, the dense MLPs, each MoE layer's router and
    shared experts, and the head over the published vocabulary."""
    d, h, hkv, dh, _ = flops._dims(cfg)
    f = cfg["moe_intermediate_size"]
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    dense = 3 * d * cfg["intermediate_size"]
    moe = d * cfg["held_experts"]["of"] \
        + 3 * d * f * cfg["n_shared_experts"]
    return (cfg["num_hidden_layers"] * attn
            + cfg["first_k_dense_replace"] * dense
            + moe_layers(cfg) * moe + d * cfg["vocab_size"])


def decode_step(cfg: dict, ctxs: Iterable[int], rows: int,
                touched: int) -> tuple:
    """(FLOPs, bytes) one decode step needs for the active sequences whose
    context lengths are ``ctxs``, whose tokens made ``rows`` assignments to
    ``touched`` held experts: 2 N per token for the weights outside the
    routed experts, the expert rows and attention, against those weights
    (the head over the padded vocabulary), the touched experts' and every
    live key and value read once."""
    ctxs = list(ctxs)
    d = cfg["hidden_size"]
    e_flops, _ = expert_kernel(cfg, rows, touched)
    fl = sum(2.0 * dense_params_per_token(cfg)
             + flops.attention_decode_flops(cfg, c) for c in ctxs) + e_flops
    weights = dense_params_per_token(cfg) \
        + d * (cfg["padded_vocab_size"] - cfg["vocab_size"]) \
        + touched * expert_params(cfg)
    return fl, weights * BF16 + flops.kv_bytes_per_token(cfg) * sum(ctxs)
