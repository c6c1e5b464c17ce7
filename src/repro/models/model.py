"""Model facade: ``build_model(arch)`` -> init / forward / loss / prefill / decode.

One code path serves all 10 assigned architectures + BERT-Large:
  dense / moe / vlm : decoder-only LM (BERT flips ``bidirectional`` + MLM head)
  ssm / hybrid      : mamba2 or interleaved stacks, same embedding/head
  encdec            : whisper — encoder over stubbed frame embeddings + causal
                      decoder with per-layer cross attention
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import transformer as tf
from .layers import (PyTree, apply_norm, dense, dense_init, embed_tokens, gelu,
                     init_embedding, init_norm, pad_vocab, sinusoidal_positions,
                     unembed)

Batch = Dict[str, jax.Array]


@dataclasses.dataclass(frozen=True)
class Model:
    arch: ArchConfig
    fuse_qkv: bool = True

    # ------------------------------------------------------------------ init ----
    def init(self, key: jax.Array) -> PyTree:
        arch = self.arch
        dtype = jnp.dtype(arch.param_dtype)
        ks = jax.random.split(key, 8)
        p: PyTree = {"embed": init_embedding(ks[0], arch.vocab_size,
                                             arch.d_model, dtype)}
        if arch.pos_emb == "learned":
            p["pos"] = {"pos_embedding":
                        (jax.random.normal(ks[1], (arch.max_position,
                                                   arch.d_model)) * 0.02
                         ).astype(dtype)}
        if arch.family == "encdec":
            p["enc_blocks"] = tf.init_stack(ks[2], arch, self.fuse_qkv, dtype,
                                            num_layers=arch.enc_layers)
            p["enc_final_norm"] = init_norm(arch.norm, arch.d_model, dtype)
            p["blocks"] = tf.init_stack(ks[3], arch, self.fuse_qkv, dtype,
                                        cross=True)
        else:
            p["blocks"] = tf.init_stack(ks[3], arch, self.fuse_qkv, dtype)
        p["final_norm"] = init_norm(arch.norm, arch.d_model, dtype)
        if not arch.tie_embeddings:
            p["out"] = {"head": dense_init(ks[4], arch.d_model,
                                           pad_vocab(arch.vocab_size), dtype)}
        if arch.mlm_transform:
            p["mlm"] = {"dense": dense_init(ks[5], arch.d_model, arch.d_model,
                                            dtype),
                        "bias": jnp.zeros((arch.d_model,), dtype),
                        "ln": init_norm(arch.norm, arch.d_model, dtype)}
        return p

    # --------------------------------------------------------------- helpers ----
    def _embed(self, p: PyTree, tokens: jax.Array, offset: int = 0) -> jax.Array:
        arch = self.arch
        dtype = jnp.dtype(arch.dtype)
        x = embed_tokens(p["embed"], tokens, dtype)
        s = tokens.shape[1]
        if arch.pos_emb == "learned":
            x = x + p["pos"]["pos_embedding"][offset:offset + s].astype(dtype)
        elif arch.pos_emb == "sinusoidal" and arch.family != "encdec":
            x = x + sinusoidal_positions(s, arch.d_model, dtype, offset)
        return x

    def _logits(self, p: PyTree, x: jax.Array) -> jax.Array:
        arch = self.arch
        with jax.named_scope("logits"):
            return self._logits_inner(p, x)

    def _logits_inner(self, p: PyTree, x: jax.Array) -> jax.Array:
        arch = self.arch
        from ..parallel.sharding import constrain
        # unshard the seq dim before the head: the model axis carries the vocab
        # sharding of logits from here on. Without this GSPMD all-gathers the
        # fp32 [B,S,V] logit cotangent (33 GB/device for command-r) instead of
        # the small [B,S,D] activations when forming the head weight grad.
        x = constrain(x, "batch", None, None)
        x = apply_norm(arch.norm, p["final_norm"], x, arch.norm_eps)
        if arch.mlm_transform:
            x = gelu(dense(x, p["mlm"]["dense"], p["mlm"]["bias"]))
            x = apply_norm(arch.norm, p["mlm"]["ln"], x, arch.norm_eps)
        tied = p["embed"]["embedding"] if arch.tie_embeddings else None
        return unembed(p.get("out", {}), x, tied, arch.logit_softcap)

    def _encode(self, p: PyTree, frontend_embeddings: jax.Array) -> jax.Array:
        """Whisper encoder over stubbed conv-frontend frame embeddings."""
        arch = self.arch
        dtype = jnp.dtype(arch.dtype)
        x = frontend_embeddings.astype(dtype)
        s = x.shape[1]
        x = x + sinusoidal_positions(s, arch.d_model, dtype)
        positions = jnp.arange(s)[None]
        x, _ = tf.apply_stack(arch, p["enc_blocks"], x, positions, causal=False)
        return apply_norm(arch.norm, p["enc_final_norm"], x, arch.norm_eps)

    # ----------------------------------------------------------------- train ----
    def forward(self, p: PyTree, batch: Batch) -> Tuple[jax.Array, jax.Array]:
        """-> (logits [B,S,Vp] fp32, aux_loss)."""
        arch = self.arch
        tokens = batch["tokens"]
        with jax.named_scope("embed"):
            x = self._embed(p, tokens)
        positions = jnp.arange(tokens.shape[1])[None]
        enc_out = None
        if arch.family == "encdec":
            enc_out = self._encode(p, batch["frontend_embeddings"])
        x, aux = tf.apply_stack(arch, p["blocks"], x, positions,
                                causal=not arch.bidirectional,
                                mrope_positions=batch.get("mrope_positions"),
                                enc_out=enc_out)
        return self._logits(p, x), aux

    def loss(self, p: PyTree, batch: Batch) -> Tuple[jax.Array, Dict[str, Any]]:
        logits, aux = self.forward(p, batch)
        with jax.named_scope("loss"):
            ce, acc = cross_entropy(logits, batch["targets"], batch.get("loss_mask"))
        total = ce + aux
        return total, {"loss": total, "ce": ce, "aux": aux, "accuracy": acc}

    # ----------------------------------------------------------------- serve ----
    def init_caches(self, p_or_none, batch: int, max_len: int) -> PyTree:
        return tf.init_caches(self.arch, batch, max_len,
                              jnp.dtype(self.arch.dtype))

    def prefill(self, p: PyTree, caches: PyTree, batch: Batch
                ) -> Tuple[jax.Array, PyTree]:
        """Fill caches from a [B, S] prompt; -> (last-position logits, caches)."""
        arch = self.arch
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = self._embed(p, tokens)
        if arch.family == "encdec":
            enc_out = self._encode(p, batch["frontend_embeddings"])
            caches = self._fill_cross_kv(p, caches, enc_out)
        positions = jnp.zeros((b,), jnp.int32)
        x, caches = tf.decode_stack(arch, p["blocks"], caches, x, positions,
                                    batch.get("mrope_positions"))
        logits = self._logits(p, x[:, -1:])
        return logits, caches

    def decode_step(self, p: PyTree, caches: PyTree, batch: Batch
                    ) -> Tuple[jax.Array, PyTree]:
        """One token for every sequence. batch: tokens [B,1], positions [B]."""
        arch = self.arch
        x = self._embed(p, batch["tokens"])
        if arch.pos_emb == "learned":
            # re-add at the right offset (decode): gather per-batch position row
            x = (embed_tokens(p["embed"], batch["tokens"], jnp.dtype(arch.dtype))
                 + p["pos"]["pos_embedding"][batch["positions"]][:, None].astype(x.dtype))
        x, caches = tf.decode_stack(arch, p["blocks"], caches, x,
                                    batch["positions"],
                                    batch.get("mrope_positions"))
        return self._logits(p, x), caches

    def _fill_cross_kv(self, p: PyTree, caches: PyTree, enc_out: jax.Array
                       ) -> PyTree:
        from . import attention as attn_lib
        arch = self.arch

        def fill(period_params, period_cache):
            for i in range(tf.period_length(arch)):
                blk = period_params[f"layer_{i}"]
                if "xattn" in blk:
                    k, v = attn_lib.project_enc_kv(arch, blk["xattn"], enc_out)
                    period_cache[f"layer_{i}"]["cross_k"] = k
                    period_cache[f"layer_{i}"]["cross_v"] = v
            return period_cache

        if isinstance(p["blocks"], dict) and any(
                k.startswith("period_") for k in p["blocks"]):
            return {z: fill(p["blocks"][z], dict(caches[z])) for z in caches}
        return jax.vmap(fill)(p["blocks"], caches)


def _ce_pieces(logits, targets, mask):
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # vocab-parallel target pick (Megatron-style): a gather over the
    # vocab-sharded axis would make GSPMD all-gather the fp32 logits; the
    # masked-sum fuses into the sharded reduce instead. Likewise accuracy via
    # max-compare — argmax lowers to a full [B,S,V] s32 iota reduce.
    vocab_ids = jnp.arange(logits.shape[-1])[None, None, :]
    onehot = vocab_ids == targets[..., None]
    target_logit = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    ll = target_logit - lse
    correct = (target_logit >= jnp.max(logits, axis=-1)).astype(jnp.float32)
    if mask is None:
        m = jnp.ones(targets.shape, jnp.float32)
    else:
        m = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(m), 1.0)
    ce = -jnp.sum(ll * m) / denom
    acc = jnp.sum(correct * m) / denom
    return ce, acc, lse, m, denom


@jax.custom_vjp
def _ce_loss(logits, targets, mask):
    ce, acc, _, _, _ = _ce_pieces(logits, targets, mask)
    return ce, acc


def _ce_loss_fwd(logits, targets, mask):
    ce, acc, lse, m, denom = _ce_pieces(logits, targets, mask)
    return (ce, acc), (logits, targets, lse, m, denom)


def _ce_loss_bwd(res, cot):
    """Hand-written vocab-parallel CE backward.

    dlogits = g * (softmax - onehot) * mask / denom, kept vocab-sharded via an
    explicit constraint — autodiff's broadcast-formed onehot cotangent anchored
    GSPMD to a *replicated* fp32 [B,S,V] (33 GB/device at command-r's 256k
    vocab; see EXPERIMENTS.md §Perf iteration log).
    """
    from ..parallel.sharding import constrain
    logits, targets, lse, m, denom = res
    g_ce, _ = cot
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    vocab_ids = jnp.arange(logits.shape[-1])[None, None, :]
    onehot = (vocab_ids == targets[..., None]).astype(jnp.float32)
    scale = (g_ce * m / denom)[..., None]
    dlogits = (p - onehot) * scale
    dlogits = constrain(dlogits, "batch", None, "vocab")
    return dlogits.astype(logits.dtype), None, None


_ce_loss.defvjp(_ce_loss_fwd, _ce_loss_bwd)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Masked softmax cross-entropy in fp32. logits [B,S,V]; targets [B,S]."""
    return _ce_loss(logits, targets, mask)


def build_model(arch: ArchConfig, fuse_qkv: bool = True) -> Model:
    return Model(arch, fuse_qkv)
