"""Plain float32 reference for ``deepseek-moe-16b.json``: the full causal
forward pass of DeepSeekMoE (arXiv:2401.06066) as one chip of its
expert-parallel deployment computes it, giving the logits at every position
of one sequence.

Each layer is pre-RMSNorm multi-head attention with RoPE (rotate-half),
then an MLP: the first ``first_k_dense_replace`` layers a SwiGLU of width
``intermediate_size``; every later layer a float32 softmax router over all
``held_experts["of"]`` published experts, the top ``num_experts_per_tok``
kept with their raw probabilities as weights (``norm_topk_prob`` false),
the weighted sum of the SwiGLU experts (width ``moe_intermediate_size``)
among them that this chip holds (the first ``n_routed_experts``), plus the
shared experts as one SwiGLU of width ``n_shared_experts *
moe_intermediate_size``. What the experts held elsewhere would add is left
out, as the program leaves it out.

It reads weights laid out as the program stores them (fused ``wqkv`` with
columns ``[q heads | k heads | v heads]``; the dense layers under
``blocks.lead``, the MoE layers under ``blocks.main`` stacked on a leading
axis, the held experts' matrices ``[held, ...]``; the head as ``[hidden,
padded vocab]``) but imports nothing of the program. ``mm`` is every matrix
product: float32 at the highest precision by default; the control passes a
lower-precision one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def rope(x, positions, theta):
    """x [S, H, Dh]: rotate the two halves of each head by position."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def swiglu(mm, x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def attention(cfg, mm, x, lp):
    s, _ = x.shape
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    pos = jnp.arange(s)
    a = lp["attn"]
    qkv = mm(rms_norm(x, lp["ln1"]["scale"], cfg["rms_norm_eps"]), a["wqkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = rope(q.reshape(s, h, dh), pos, cfg["rope_theta"])
    k = rope(k.reshape(s, h, dh), pos, cfg["rope_theta"])
    v = v.reshape(s, h, dh)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(dh))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v,
                   precision=HIGHEST).reshape(s, h * dh)
    return x + mm(o, a["wo"])


def dense_layer(cfg, mm, x, lp):
    x = attention(cfg, mm, x, lp)
    m = lp["mlp"]
    return x + swiglu(mm, rms_norm(x, lp["ln2"]["scale"], cfg["rms_norm_eps"]),
                      m["w1"], m["w3"], m["w2"])


def moe_layer(cfg, mm, x, lp):
    x = attention(cfg, mm, x, lp)
    h = rms_norm(x, lp["ln2"]["scale"], cfg["rms_norm_eps"])
    m = lp["moe"]
    probs = jax.nn.softmax(mm(h, m["router"]), -1)           # [S, E]
    top_w, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    ex = m["experts"]
    y = swiglu(mm, h, m["shared"]["w1"], m["shared"]["w3"],
               m["shared"]["w2"])
    for j in range(cfg["n_routed_experts"]):
        gate = jnp.sum(jnp.where(top_i == j, top_w, 0.0), -1)
        y = y + gate[:, None] * swiglu(mm, h, ex["w1"][j], ex["w3"][j],
                                       ex["w2"][j])
    return x + y


def stacked(tree):
    """Layers on a leading axis, whether stored so or one dict a layer."""
    if "period_0" in tree:
        periods = [tree[f"period_{z}"]["layer_0"] for z in range(len(tree))]
        return jax.tree.map(lambda *ls: jnp.stack(ls), *periods)
    return tree["layer_0"]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits(cfg_items, mm, params, tokens):
    cfg = dict(cfg_items)
    x = params["embed"]["embedding"][tokens]
    for layer, part in ((dense_layer, "lead"), (moe_layer, "main")):
        def body(x, lp, layer=layer):
            return layer(cfg, mm, x, lp), None
        x, _ = jax.lax.scan(body, x, stacked(params["blocks"][part]))
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return mm(x, params["out"]["head"])


def hashable(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "num_experts_per_tok", "norm_topk_prob", "n_routed_experts")
    return tuple((k, cfg[k]) for k in keys)


def logits(cfg, params, tokens, mm=mm_f32, bucket=512):
    """[S, padded vocab] float32 logits of one sequence ``tokens`` [S].
    The sequence is padded at its end to a multiple of ``bucket`` (causal
    attention, and routing per position: no position reads the padding) so
    that few lengths compile."""
    s = len(tokens)
    padded = jnp.zeros((-(-s // bucket) * bucket,), jnp.int32).at[:s].set(
        jnp.asarray(tokens, jnp.int32))
    return _logits(hashable(cfg), mm, params, padded)[:s]
