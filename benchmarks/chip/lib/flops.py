"""Operations and bytes that the algorithm needs, from the configuration's
shapes. Counted by hand from the layer equations, never read from the
program or from XLA: these are the yardstick a roofline share divides by.

``cfg`` is a configuration file's dict (``configs/<name>.json``).
"""
from __future__ import annotations

from typing import Iterable

BF16 = 2


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hkv = cfg.get("num_key_value_heads", h)
    dh = cfg.get("head_dim", d // h)
    f = cfg["intermediate_size"]
    return d, h, hkv, dh, f


def layer_matmul_params(cfg: dict) -> int:
    """Weights one layer multiplies by: the q, k, v and output projections
    and the MLP (three matrices for a gated MLP, two otherwise)."""
    d, h, hkv, dh, f = _dims(cfg)
    attn = d * h * dh + 2 * d * hkv * dh + h * dh * d
    mlp = (3 if cfg.get("gated_mlp") else 2) * d * f
    return attn + mlp


def matmul_params(cfg: dict) -> int:
    """Every weight a token is multiplied by: the layers, an MLM transform
    where there is one, and the output head over the published vocabulary
    (counted once where it is tied to the embedding). The embedding lookup
    and position table are no matmul."""
    d = cfg["hidden_size"]
    n = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    if cfg.get("mlm_transform"):
        n += d * d
    return n + d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, without recomputation: 6 N for the matmuls
    plus 12 L s d for attention's two score products (QK^T and PV at 2 s d
    each, forward, times three with the backward), every token attending to
    all ``seq_len`` positions (bidirectional)."""
    d, h, _, dh, _ = _dims(cfg)
    attn = 12 * cfg["num_hidden_layers"] * seq_len * h * dh
    return 6.0 * matmul_params(cfg) + attn


def weight_bytes(cfg: dict, dtype_bytes: int = BF16) -> int:
    """Bytes of the weights one decode step reads once: every matmul weight
    with the head over the padded vocabulary it is stored as."""
    d = cfg["hidden_size"]
    n = cfg["num_hidden_layers"] * layer_matmul_params(cfg)
    return (n + d * cfg["padded_vocab_size"]) * dtype_bytes


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = BF16) -> int:
    """Keys and values of one cached token over all layers."""
    _, _, hkv, dh, _ = _dims(cfg)
    return cfg["num_hidden_layers"] * 2 * hkv * dh * dtype_bytes


def attention_decode_flops(cfg: dict, ctx: int) -> float:
    """One query token against ``ctx`` cached tokens, all layers: QK^T and
    PV at 2 ctx dh per query head each."""
    _, h, _, dh, _ = _dims(cfg)
    return 4.0 * cfg["num_hidden_layers"] * h * dh * ctx


def decode_step(cfg: dict, ctxs: Iterable[int]) -> tuple:
    """(FLOPs, bytes) one decode step needs for the active sequences whose
    context lengths are ``ctxs``: 2 N per token plus attention, against the
    weights read once plus every live key and value read once."""
    ctxs = list(ctxs)
    flops = sum(2.0 * matmul_params(cfg) + attention_decode_flops(cfg, c)
                for c in ctxs)
    nbytes = weight_bytes(cfg) + kv_bytes_per_token(cfg) * sum(ctxs)
    return flops, nbytes


def paged_attention(cfg: dict, ctxs: Iterable[int]) -> tuple:
    """(FLOPs, bytes) of the paged decode attention kernel over all layers
    for one step: the live keys and values read once, the queries read and
    the outputs written once per active sequence."""
    ctxs = list(ctxs)
    _, h, _, dh, _ = _dims(cfg)
    flops = sum(attention_decode_flops(cfg, c) for c in ctxs)
    q_and_out = cfg["num_hidden_layers"] * 2 * h * dh * BF16 * len(ctxs)
    return flops, kv_bytes_per_token(cfg) * sum(ctxs) + q_and_out


def lm_head(cfg: dict, rows: int) -> tuple:
    """(FLOPs, bytes) of the LM head over the padded vocabulary for
    ``rows`` hidden states: 2 rows d V, the head weight read once and the
    hidden states read once."""
    d = cfg["hidden_size"]
    v = cfg["padded_vocab_size"]
    return 2.0 * rows * d * v, d * v * BF16 + rows * d * BF16


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds, which bound): the larger of FLOPs over the bf16 peak
    and bytes over the HBM bandwidth."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
