"""Plain float32 reference for ``internlm2-1.8b.json``: the full causal
forward pass of a decoder with grouped-query attention, RoPE and a SwiGLU
MLP under pre-RMSNorm (InternLM2, arXiv:2403.17297), giving the logits at
every position of one sequence.

It reads weights laid out as the program stores them (fused ``wqkv`` with
columns ``[q heads | k heads | v heads]``, query head ``i`` reading key
head ``i // (heads / kv_heads)``, layers stacked on a leading axis, the
head as ``[hidden, padded vocab]``) but imports nothing of the program.
``mm`` is every matrix product: float32 at the highest precision by
default; the control passes a lower-precision one.
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


def decoder_layer(cfg, mm, x, lp):
    s, d = x.shape
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    a = lp["attn"]
    qkv = mm(rms_norm(x, lp["ln1"]["scale"], eps), a["wqkv"])
    q, k, v = jnp.split(qkv, [h * dh, h * dh + hkv * dh], axis=-1)
    q = rope(q.reshape(s, h, dh), pos, cfg["rope_theta"])
    k = rope(k.reshape(s, hkv, dh), pos, cfg["rope_theta"])
    v = v.reshape(s, hkv, dh)
    qg = q.reshape(s, hkv, h // hkv, dh)
    scores = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HIGHEST) \
        / jnp.sqrt(jnp.float32(dh))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, -1), v,
                   precision=HIGHEST).reshape(s, h * dh)
    x = x + mm(o, a["wo"])
    hm = rms_norm(x, lp["ln2"]["scale"], eps)
    m = lp["mlp"]
    return x + mm(jax.nn.silu(mm(hm, m["w1"])) * mm(hm, m["w3"]), m["w2"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits(cfg_items, mm, params, tokens):
    cfg = dict(cfg_items)
    x = params["embed"]["embedding"][tokens]

    def body(x, lp):
        return decoder_layer(cfg, mm, x, lp), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["layer_0"])
    x = rms_norm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    return mm(x, params["out"]["head"])


def hashable(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)


def logits(cfg, params, tokens, mm=mm_f32, bucket=512):
    """[S, padded vocab] float32 logits of one sequence ``tokens`` [S].
    The sequence is padded at its end to a multiple of ``bucket`` (causal
    attention: no position reads the padding) so that few lengths compile."""
    s = len(tokens)
    padded = jnp.zeros((-(-s // bucket) * bucket,), jnp.int32).at[:s].set(
        jnp.asarray(tokens, jnp.int32))
    return _logits(hashable(cfg), mm, params, padded)[:s]
