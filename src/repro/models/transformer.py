"""Block definitions and layer stacks.

Layers are stacked by *period*: the smallest repeating group of layers.
  dense/ssm/deepseek-moe : period 1
  llama4 (interleaved)   : period 2 (dense MLP, then MoE)
  jamba                  : period 8 (mamba x4, attn@4, mamba x3; MoE on odd layers)
Stacked parameters have a leading [num_periods, ...] axis and are consumed by
``jax.lax.scan`` (compile-time: one period lowered once — essential for 88-layer
models on the 512-device dry-run). ``arch.remat`` wraps the period body in
``jax.checkpoint`` so live activations are one [B, S, D] residual per period.

Leading dense layers (deepseek-moe's layer 0, ahead of ``moe.first``) are no
part of any period: such a stack is ``{"lead": <dense stack>, "main":
<period stack>}`` in parameters, caches and serving state alike, and every
stack function runs the lead stack, then the main one (``split_lead``).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..kernels.fused_layernorm import ops as ln_ops
from ..parallel.sharding import constrain
from . import attention as attn_lib
from . import moe as moe_lib
from . import ssm as ssm_lib
from .layers import PyTree, apply_mlp, apply_norm, init_mlp, init_norm

REMAT_POLICY = jax.checkpoint_policies.nothing_saveable
# the expert counters of a step without MoE layers (``moe.expert_counts``)
NO_COUNTS = np.zeros((2,), np.int32)


# --------------------------------------------------------------------- periods ----

def period_length(arch: ArchConfig) -> int:
    if arch.family == "hybrid":
        return arch.hybrid_period
    if arch.moe is not None and arch.moe.every > 1:
        return arch.moe.every
    return 1


def lead_layers(arch: ArchConfig) -> int:
    """Dense layers ahead of the first period: those before ``moe.first``,
    in whole periods (0 where the first MoE layer lies in the first
    period, as in an interleaved stack)."""
    if arch.moe is None:
        return 0
    plen = period_length(arch)
    return arch.moe.first // plen * plen


def split_lead(arch: ArchConfig) -> Tuple[ArchConfig, ArchConfig]:
    """(lead stack's arch: its dense layers; main stack's arch: the
    layers from the first period on) of a stack with leading layers."""
    n = lead_layers(arch)
    lead = dataclasses.replace(arch, num_layers=n, moe=None)
    main = dataclasses.replace(
        arch, num_layers=arch.num_layers - n,
        moe=dataclasses.replace(arch.moe, first=arch.moe.first - n))
    return lead, main


def layer_kinds(arch: ArchConfig) -> Tuple[Tuple[str, bool], ...]:
    """Per layer within one period (of the main stack, past any leading
    layers): (mixer kind, has_moe)."""
    lead = lead_layers(arch)
    out = []
    for i in range(period_length(arch)):
        mixer = "attn" if arch.is_attention_layer(lead + i) else "mamba"
        out.append((mixer, arch.is_moe_layer(lead + i)))
    return tuple(out)


# ------------------------------------------------------------------------- init ---

def init_block(key, arch: ArchConfig, mixer: str, has_moe: bool,
               fuse_qkv: bool, dtype, cross: bool = False) -> PyTree:
    ks = jax.random.split(key, 4)
    p: PyTree = {"ln1": init_norm(arch.norm, arch.d_model, dtype)}
    if mixer == "attn":
        p["attn"] = attn_lib.init_attention(ks[0], arch, fuse_qkv, dtype=dtype)
    else:
        p["mamba"] = ssm_lib.init_mamba(ks[0], arch, dtype)
    if cross:
        p["ln_x"] = init_norm(arch.norm, arch.d_model, dtype)
        p["xattn"] = attn_lib.init_attention(ks[2], arch, fuse_qkv=False,
                                             cross=True, dtype=dtype)
    if arch.family == "ssm":
        return p  # mamba2 blocks have no MLP
    p["ln2"] = init_norm(arch.norm, arch.d_model, dtype)
    if has_moe:
        p["moe"] = moe_lib.init_moe(ks[1], arch, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], arch.mlp, arch.d_model, arch.d_ff,
                            arch.use_bias, dtype)
    return p


def init_period(key, arch: ArchConfig, fuse_qkv: bool, dtype,
                cross: bool = False) -> PyTree:
    kinds = layer_kinds(arch)
    ks = jax.random.split(key, len(kinds))
    return {f"layer_{i}": init_block(ks[i], arch, mixer, has_moe, fuse_qkv,
                                     dtype, cross)
            for i, (mixer, has_moe) in enumerate(kinds)}


def init_stack(key, arch: ArchConfig, fuse_qkv: bool, dtype,
               num_layers: Optional[int] = None, cross: bool = False) -> PyTree:
    if lead_layers(arch) and num_layers is None:
        return _init_lead_stack(key, arch, fuse_qkv, dtype, cross)
    plen = period_length(arch) if not cross else 1
    nl = num_layers if num_layers is not None else arch.num_layers
    assert nl % plen == 0, (arch.name, nl, plen)
    nper = nl // plen
    keys = jax.random.split(key, nper)
    if arch.scan_layers and nper > 1:
        return jax.vmap(
            lambda k: init_period(k, arch, fuse_qkv, dtype, cross))(keys)
    return {f"period_{z}": init_period(keys[z], arch, fuse_qkv, dtype, cross)
            for z in range(nper)}


def _init_lead_stack(key, arch: ArchConfig, fuse_qkv: bool, dtype,
                     cross: bool) -> PyTree:
    lead, main = split_lead(arch)
    k1, k2 = jax.random.split(key)
    return {"lead": init_stack(k1, lead, fuse_qkv, dtype, cross=cross),
            "main": init_stack(k2, main, fuse_qkv, dtype, cross=cross)}


# ------------------------------------------------------------------ block apply ---

def fused_blocks_enabled() -> bool:
    """Training/prefill block fusion (``fused_residual_layernorm`` +
    ``bias_gelu``) — default OFF. Unlike fused *decode* this is a
    tolerance-parity path, not a bit-parity one: the residual+norm kernel
    adds in fp32 where the unfused block adds in model dtype, so bf16
    training losses match to rounding, not bitwise."""
    return os.environ.get("REPRO_FUSED_BLOCKS", "0") == "1"


def apply_block(arch: ArchConfig, p: PyTree, x: jax.Array, mixer: str,
                positions: jax.Array, causal: bool, mrope_positions=None,
                enc_out=None,
                fused: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm (or BERT post-norm) residual block. Returns (y, aux_loss).

    ``fused`` (None = read ``REPRO_FUSED_BLOCKS``, default off) routes the
    residual-add + norm pairs through ``kernels.fused_layernorm`` — the
    post-norm sites through ``fused_residual_layernorm`` (the Fig-13 BERT
    pattern: add + stats + normalize in one VMEM pass), the pre-norm
    mixer-add + ln2 pair through ``decode_residual_norm`` — and the gelu
    MLP's bias+activation through ``kernels.bias_gelu``. Tolerance parity
    with the unfused block (``tests/test_kernels.py`` pins it); training
    and chunked prefill only — the decode path has its own bit-exact
    fusion (``paged_decode_period``)."""
    if fused is None:
        fused = fused_blocks_enabled()
    aux = jnp.zeros((), jnp.float32)
    rms = arch.norm == "rmsnorm"

    def mix(h):
        if mixer == "attn":
            return attn_lib.apply_attention(arch, p["attn"], h, positions,
                                            causal=causal,
                                            mrope_positions=mrope_positions)
        return ssm_lib.apply_mamba(arch, p["mamba"], h)

    # pre-norm ln2 can absorb the mixer's residual add; not when the block
    # has a cross-attention insert between the two sites, and not for ssm
    # blocks (no ln2 exists)
    fuse_pre_ln2 = (fused and not arch.post_norm and arch.family != "ssm"
                    and not (enc_out is not None and "xattn" in p))
    h = None
    if arch.post_norm:
        y = mix(x)
        if fused:
            x = ln_ops.fused_residual_layernorm(
                y, x, p["ln1"]["scale"], p["ln1"].get("bias"),
                eps=arch.norm_eps, rms=rms)
        else:
            x = apply_norm(arch.norm, p["ln1"], x + y, arch.norm_eps)
    elif fuse_pre_ln2:
        y = mix(apply_norm(arch.norm, p["ln1"], x, arch.norm_eps))
        h, x = ln_ops.decode_residual_norm(
            y, x, p["ln2"]["scale"], p["ln2"].get("bias"), kind=arch.norm,
            eps=arch.norm_eps)
    else:
        x = x + mix(apply_norm(arch.norm, p["ln1"], x, arch.norm_eps))

    if enc_out is not None and "xattn" in p:
        h = apply_norm(arch.norm, p["ln_x"], x, arch.norm_eps)
        enc_kv = attn_lib.project_enc_kv(arch, p["xattn"], enc_out)
        x = x + attn_lib.apply_cross_attention(arch, p["xattn"], h, enc_kv)
        h = None

    if arch.family == "ssm":
        return x, aux

    if arch.post_norm:
        if "moe" in p:
            y, aux = moe_lib.apply_moe(arch, p["moe"], x)
        else:
            y = apply_mlp(arch.mlp, p["mlp"], x, fused=fused)
        if fused:
            x = ln_ops.fused_residual_layernorm(
                y, x, p["ln2"]["scale"], p["ln2"].get("bias"),
                eps=arch.norm_eps, rms=rms)
        else:
            x = apply_norm(arch.norm, p["ln2"], x + y, arch.norm_eps)
    else:
        if h is None:
            h = apply_norm(arch.norm, p["ln2"], x, arch.norm_eps)
        if "moe" in p:
            y, aux = moe_lib.apply_moe(arch, p["moe"], h)
        else:
            y = apply_mlp(arch.mlp, p["mlp"], h, fused=fused)
        x = x + y
    return x, aux


def apply_period(arch: ArchConfig, p: PyTree, x: jax.Array,
                 positions: jax.Array, causal: bool, mrope_positions=None,
                 enc_out=None) -> Tuple[jax.Array, jax.Array]:
    aux_total = jnp.zeros((), jnp.float32)
    for i, (mixer, _) in enumerate(layer_kinds(arch)):
        # sequence-parallel residual stream between blocks (DESIGN.md §4)
        x = constrain(x, "batch", "seq", "embed")
        blk = functools.partial(apply_block, arch, mixer=mixer,
                                positions=positions, causal=causal,
                                mrope_positions=mrope_positions,
                                enc_out=enc_out)
        if arch.remat:
            # per-block remat: backward recomputes one block's internals at a
            # time; only the [B,S,D] residual per block stays live
            blk = jax.checkpoint(blk, policy=REMAT_POLICY)
        x, aux = blk(p[f"layer_{i}"], x)
        aux_total = aux_total + aux
    return constrain(x, "batch", "seq", "embed"), aux_total


# ----------------------------------------------------------------- stack apply ----

def apply_stack(arch: ArchConfig, stacked: PyTree, x: jax.Array,
                positions: jax.Array, causal: bool, mrope_positions=None,
                enc_out=None) -> Tuple[jax.Array, jax.Array]:
    if lead_layers(arch):
        lead, main = split_lead(arch)
        x, aux0 = apply_stack(lead, stacked["lead"], x, positions, causal,
                              mrope_positions, enc_out)
        x, aux1 = apply_stack(main, stacked["main"], x, positions, causal,
                              mrope_positions, enc_out)
        return x, aux0 + aux1
    body = functools.partial(apply_period, arch, positions=positions,
                             causal=causal, mrope_positions=mrope_positions,
                             enc_out=enc_out)

    if isinstance(stacked, dict) and any(k.startswith("period_") for k in stacked):
        aux_total = jnp.zeros((), jnp.float32)
        for z in range(len(stacked)):
            x, a = body(stacked[f"period_{z}"], x)
            aux_total = aux_total + a
        return x, aux_total

    def scan_body(carry, period_params):
        h, aux = carry
        h, a = body(period_params, h)
        return (h, aux + a), None
    (x, aux), _ = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# ------------------------------------------------------------------ decode path ---

def init_caches(arch: ArchConfig, batch: int, max_len: int, dtype) -> PyTree:
    """Stacked per-period decode caches (incl. whisper cross-KV)."""
    if lead_layers(arch):
        return {part: init_caches(a, batch, max_len, dtype)
                for part, a in zip(("lead", "main"), split_lead(arch))}

    def one_period():
        c: PyTree = {}
        for i, (mixer, _) in enumerate(layer_kinds(arch)):
            if mixer == "attn":
                c[f"layer_{i}"] = attn_lib.init_kv_cache(arch, batch, max_len, dtype)
                if arch.family == "encdec":
                    hd = arch.resolved_head_dim
                    c[f"layer_{i}"]["cross_k"] = jnp.zeros(
                        (batch, arch.enc_seq_len, arch.num_kv_heads, hd), dtype)
                    c[f"layer_{i}"]["cross_v"] = jnp.zeros(
                        (batch, arch.enc_seq_len, arch.num_kv_heads, hd), dtype)
            else:
                c[f"layer_{i}"] = ssm_lib.init_mamba_cache(arch, batch, dtype)
        return c
    nper = arch.num_layers // period_length(arch)
    per = one_period()
    if arch.scan_layers and nper > 1:
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (nper,) + l.shape).copy(), per)
    return {f"period_{z}": one_period() for z in range(nper)}


def init_serving_state(arch: ArchConfig, num_pages: int, page_size: int,
                       num_slots: int, dtype) -> PyTree:
    """Per-layer decode state for the continuous engine, stacked like
    ``init_caches`` — the decode-state protocol's device side.

    Each layer kind declares its own state:

    - ``attn``  : a paged KV pool ``{k, v}: [P, page, Hkv, Dh]``. Every
      attention layer shares one logical page table — a sequence's page ids
      index the same rows of every layer's pool, so the allocator hands out
      ids once and the whole stack follows (vLLM's layout).
    - ``mamba`` : a pooled, constant-size per-*slot* state
      ``{conv: [slot, W-1, C], state: [slot, H, N, P]}`` — the recurrence
      folds all history into fixed-size state, so it is allocated per decode
      slot, not per page, and costs nothing as context grows.
    """
    assert arch.family != "encdec", "paged path has no cross-attention cache"
    if lead_layers(arch):
        return {part: init_serving_state(a, num_pages, page_size, num_slots,
                                         dtype)
                for part, a in zip(("lead", "main"), split_lead(arch))}
    kinds = layer_kinds(arch)

    def layer_state(mixer):
        if mixer == "attn":
            return attn_lib.init_paged_kv_cache(arch, num_pages, page_size,
                                                dtype)
        return ssm_lib.init_mamba_cache(arch, num_slots, dtype)

    def one_period():
        return {f"layer_{i}": layer_state(m)
                for i, (m, _) in enumerate(kinds)}
    nper = arch.num_layers // period_length(arch)
    if arch.scan_layers and nper > 1:
        per = one_period()
        return jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (nper,) + l.shape).copy(), per)
    return {f"period_{z}": one_period() for z in range(nper)}


def init_paged_caches(arch: ArchConfig, num_pages: int, page_size: int,
                      dtype) -> PyTree:
    """Attention-only page pools (the pre-protocol surface, kept for callers
    that size pure KV pools); mixed stacks go through ``init_serving_state``.
    """
    kinds = layer_kinds(arch)
    assert all(m == "attn" for m, _ in kinds), \
        f"paged caches need attention-only stacks, got {kinds} ({arch.name})"
    return init_serving_state(arch, num_pages, page_size, 0, dtype)


def _decode_block_mix(arch: ArchConfig, blk: PyTree, x: jax.Array, mix_fn
                      ) -> Tuple[jax.Array, PyTree]:
    """Shared pre/post-norm residual wrapping of a decode mixer.
    ``mix_fn(h) -> (y, new_cache)``."""
    eps = arch.norm_eps
    h = x if arch.post_norm else apply_norm(arch.norm, blk["ln1"], x, eps)
    y, new_c = mix_fn(h)
    x = apply_norm(arch.norm, blk["ln1"], x + y, eps) if arch.post_norm \
        else x + y
    return x, new_c


def _decode_block_ffn(arch: ArchConfig, blk: PyTree, x: jax.Array,
                      tp_axis: Optional[str] = None,
                      moe_eff_cap: Optional[jax.Array] = None,
                      rows: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Shared MoE/MLP tail of a decode block (no-op for mamba2 blocks).
    ``tp_axis``: serving tensor parallelism — the MLP runs on Megatron
    shards and psums its row-parallel output; a MoE block runs its
    expert-parallel path (experts sharded on the leading axis, one psum on
    the combine). ``moe_eff_cap`` (prefill chunks): the full prompt's
    capacity, so drops match the static engine's full-prompt dispatch
    rather than a bucket inflated by the chunk's padded shape. ``rows``
    [B, S]: the rows that hold a token (MoE layers route only those).
    Returns ``(x, counts)``: the MoE layer's expert counters
    (``moe.serve_moe``), zeros for any other block."""
    counts = NO_COUNTS
    if arch.family == "ssm":
        return x, counts
    eps = arch.norm_eps
    h = x if arch.post_norm else apply_norm(arch.norm, blk["ln2"], x, eps)
    if "moe" in blk:
        y, counts = moe_lib.serve_moe(arch, blk["moe"], h, tp_axis,
                                      moe_eff_cap, rows)
    else:
        y = apply_mlp(arch.mlp, blk["mlp"], h, tp_axis)
    x = apply_norm(arch.norm, blk["ln2"], x + y, eps) if arch.post_norm \
        else x + y
    return x, counts


def _fused_residual_norm(arch: ArchConfig, ln: PyTree, d: jax.Array,
                         x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Fold the pending residual delta ``d`` into the stream and norm it in
    one fused pass: ``x += d; h = norm(x)`` -> ``(h, x_new)``. Bit-identical
    to the unfused two-op sequence (see ``kernels.fused_layernorm.ref``)."""
    return ln_ops.decode_residual_norm(d, x, ln["scale"], ln.get("bias"),
                                       kind=arch.norm, eps=arch.norm_eps)


def _fused_block_delta(arch: ArchConfig, blk: PyTree, h: jax.Array,
                       tp_axis: Optional[str] = None,
                       moe_eff_cap: Optional[jax.Array] = None,
                       rows: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """MoE/MLP tail of a fused decode block: returns the residual *delta*
    (the add is deferred into the next in-period layer's fused pre-norm, or
    into the period-end boundary add for the last layer) and the expert
    counters, as ``_decode_block_ffn``."""
    if "moe" in blk:
        return moe_lib.serve_moe(arch, blk["moe"], h, tp_axis, moe_eff_cap,
                                 rows)
    return apply_mlp(arch.mlp, blk["mlp"], h, tp_axis), NO_COUNTS


def paged_decode_period(arch: ArchConfig, p: PyTree, cache: PyTree,
                        x: jax.Array, page_table: jax.Array,
                        seq_lens: jax.Array, mrope_positions=None,
                        tp_axis: Optional[str] = None,
                        fused: bool = False,
                        z: Optional[jax.Array] = None
                        ) -> Tuple[jax.Array, PyTree, jax.Array]:
    """One period of single-token decode. ``fused=True`` carries the
    residual stream through the period as an ``(x, pending-delta)`` pair:
    every residual-add + pre-norm pair collapses into one
    ``decode_residual_norm`` pass (the mixer add at each ln2 site; the
    previous layer's MLP delta at each ln1 site of a multi-layer period),
    so the residual stream makes one HBM round-trip per fused site instead
    of three (add-out, norm-read, delta-write). The pending delta is folded
    by a plain add before returning — the period's carry interface (and,
    bitwise, its result: the fused kernels duplicate the unfused op
    sequence exactly, and the boundary add sits at the same graph position
    as the unfused path's, which keeps XLA's context-sensitive fusion
    choices identical across the two variants) matches ``fused=False``.
    Pre-norm stacks only. Returns ``(x, new cache, expert counters)``: the
    period's MoE counters summed (``moe.serve_moe``; empty slots route
    nowhere).

    With ``z`` (the layer scan's period index) ``cache`` is the scan's whole
    stack of periods, every leaf with a leading period axis, and comes back
    as that stack with period ``z``'s state updated: attention pools are
    written and read at ``z`` in place, mamba slot state (a few KB a slot)
    is read at ``z`` and set back there."""
    if fused:
        assert not arch.post_norm, (arch.name, "fused decode is pre-norm only")
    new_cache: PyTree = {}
    counts = NO_COUNTS
    d: Optional[jax.Array] = None     # pending in-period residual delta
    # a slot with seq_len 0 is empty or mid-prefill: attention routes its
    # writes to the null page; mamba layers must instead keep their state row
    active = seq_lens > 0
    rows = active[:, None]
    for i, (mixer, _) in enumerate(layer_kinds(arch)):
        x = constrain(x, "batch", None, None)
        blk = p[f"layer_{i}"]

        def mix(h, blk=blk, i=i, mixer=mixer):
            c = cache[f"layer_{i}"]
            if mixer == "attn":
                return attn_lib.paged_decode_attention_layer(
                    arch, blk["attn"], h, c, page_table, seq_lens,
                    mrope_positions, tp_axis, layer=z)
            if z is None:
                return ssm_lib.paged_decode_mamba_layer(
                    arch, blk["mamba"], h, c, active)
            y, new = ssm_lib.paged_decode_mamba_layer(
                arch, blk["mamba"], h, jax.tree.map(lambda a: a[z], c),
                active)
            return y, jax.tree.map(lambda a, n: a.at[z].set(n), c, new)
        if fused:
            if d is None:
                h = apply_norm(arch.norm, blk["ln1"], x, arch.norm_eps)
            else:
                h, x = _fused_residual_norm(arch, blk["ln1"], d, x)
            y, new_cache[f"layer_{i}"] = mix(h)
            if arch.family == "ssm":
                d = y  # mamba2 blocks have no MLP: y is the pending delta
            else:
                h2, x = _fused_residual_norm(arch, blk["ln2"], y, x)
                d, c = _fused_block_delta(arch, blk, h2, tp_axis, rows=rows)
                counts = counts + c
        else:
            x, new_cache[f"layer_{i}"] = _decode_block_mix(arch, blk, x, mix)
            x, c = _decode_block_ffn(arch, blk, x, tp_axis, rows=rows)
            counts = counts + c
    if fused:
        x = x + d
    return x, new_cache, counts


def paged_decode_stack(arch: ArchConfig, stacked: PyTree, caches: PyTree,
                       x: jax.Array, page_table: jax.Array,
                       seq_lens: jax.Array, mrope_positions=None,
                       tp_axis: Optional[str] = None,
                       fused: bool = False):
    """Single-token decode through the whole stack. ``fused=True`` runs the
    residual+norm-fused period bodies; the carry between periods is the
    plain completed residual either way (bit-identical to ``fused=False`` —
    the fused body keeps every residual add at the same graph position, so
    XLA's context-sensitive lowering of the norm reductions matches).
    Pre-norm stacks only. Returns ``(x, new caches, expert counters)``:
    int32 [2], the token-to-held-expert assignments of the step's MoE
    layers and, summed over layers, the held experts with at least one
    (zeros without MoE layers)."""
    if fused:
        assert not arch.post_norm, (arch.name, "fused decode is pre-norm only")
    if lead_layers(arch):
        lead, main = split_lead(arch)
        x, lc, c0 = paged_decode_stack(lead, stacked["lead"], caches["lead"],
                                       x, page_table, seq_lens,
                                       mrope_positions, tp_axis, fused)
        x, mc, c1 = paged_decode_stack(main, stacked["main"], caches["main"],
                                       x, page_table, seq_lens,
                                       mrope_positions, tp_axis, fused)
        return x, {"lead": lc, "main": mc}, c0 + c1
    if isinstance(stacked, dict) and any(k.startswith("period_") for k in stacked):
        new_caches: PyTree = {}
        counts = NO_COUNTS
        for z in range(len(stacked)):
            x, nc, c = paged_decode_period(
                arch, stacked[f"period_{z}"], caches[f"period_{z}"],
                x, page_table, seq_lens, mrope_positions,
                tp_axis, fused=fused)
            new_caches[f"period_{z}"] = nc
            counts = counts + c
        return x, new_caches, counts

    # the stacked state rides in the carry, never in xs / ys: scanned, XLA
    # would slice each period's pools out of the stack, write them back
    # into a new one and copy that whole stack out of the loop every step
    def scan_body(carry, period_params):
        h, counts, z, caches = carry
        h, caches, c = paged_decode_period(
            arch, period_params, caches, h, page_table, seq_lens,
            mrope_positions, tp_axis, fused=fused, z=z)
        return (h, counts + c, z + 1, caches), None
    (x, counts, _, caches), _ = jax.lax.scan(
        scan_body, (x, NO_COUNTS, jnp.zeros((), jnp.int32), caches), stacked)
    return x, caches, counts


# ---- multi-step compiled decode -------------------------------------------
# Per-slot exit-reason bits returned by `paged_decode_loop`. A dispatch that
# ran the full horizon with no bit set exited on the N-step horizon alone.
EXIT_EOS = 1        # slot emitted its request's eos token
EXIT_BUDGET = 2     # slot emitted its last allowed token (max-new / context)
EXIT_PAGES = 4      # slot's next K/V write would fall past its allocated pages


def paged_decode_loop(arch: ArchConfig, stacked: PyTree, caches: PyTree,
                      tokens: jax.Array, page_table: jax.Array,
                      seq_lens: jax.Array, active: jax.Array,
                      budget: jax.Array, page_limit: jax.Array,
                      eos_ids: jax.Array, *, horizon: int, embed, unembed,
                      select, probe: bool = False,
                      tp_axis: Optional[str] = None, fused_head=None):
    """Up to ``horizon`` decode iterations in one on-device ``lax.while_loop``.

    The loop body is exactly one single-step decode (``paged_decode_stack``
    + LM head + the caller's token selection) with the carry advanced the
    way the host would have between dispatches: ``seq_lens`` increments for
    active slots each iteration, and the sampling position handed to
    ``select`` is the carried ``seq_lens + 1`` — so the (seed, position)
    PRNG key of every draw matches the single-step engine bit-for-bit at
    any horizon, including across forced-replay preemption.

    Carry: ``(i, tokens, seq_lens, caches, emitted buffer [horizon, S],
    exit-reason bits [S], finite-probe ok)``. The loop exits as soon as ANY
    slot records an exit event, so events can only be set on the final
    executed iteration and every one of the ``i`` returned iterations is
    valid for every active slot — the host appends exactly ``i`` tokens per
    slot and never sees a token past a slot's EOS.

    Exit predicates (the in-loop restatement of the host scheduler's
    per-token decisions):

    - ``EXIT_EOS``:    the token just emitted equals the slot's ``eos_ids``
                       entry (-1 for requests without one — never matches).
    - ``EXIT_BUDGET``: the slot emitted its last allowed token
                       (``budget[s]`` = host-computed remaining max-new /
                       context-capacity allowance).
    - ``EXIT_PAGES``:  checked *before* an iteration — an active slot's
                       next K/V write position (= its carried ``seq_lens``)
                       would land past ``page_limit[s]`` (allocated pages ×
                       page size). Computed again after the loop so the
                       host sees which slot needs a page, not just that the
                       loop stopped.

    Returns ``(buf [horizon, S], steps, reasons [S], caches[, ok])`` with
    ``steps >= 1`` (the host guarantees iteration 0's predicates hold).
    ``embed``/``unembed`` are the model's token embedding / LM head;
    ``select(logits [S, V], positions [S]) -> int32 [S]`` picks tokens
    (argmax or the fused-sampling epilogue) from the in-carry positions.
    ``fused_head(x, positions) -> (tokens [S], ok [S])`` replaces
    ``unembed`` + ``select`` on the fused-decode path: the final hidden
    state from ``paged_decode_stack(fused=True)`` goes straight into the
    streaming final-norm + LM-head epilogue, no [S, V] logits buffer ever
    exists, and the finite probe rides out of the epilogue's in-register
    sweep instead of scanning materialized logits.
    Inactive slots (mid-prefill or empty, masked to the null page) never
    advance ``seq_lens``, never set exit bits, and their junk draws are
    discarded by the host.
    """
    n_slots = tokens.shape[0]

    def _cond(carry):
        i, _tok, lens, _caches, _buf, reasons, _ok = carry
        blocked = active & (lens >= page_limit)
        return (i < horizon) & jnp.all(reasons == 0) & ~jnp.any(blocked)

    def _body(carry):
        i, tok, lens, caches, buf, reasons, ok = carry
        x = embed(tok[:, None])
        if fused_head is not None:
            x, caches, _ = paged_decode_stack(
                arch, stacked, caches, x, page_table, lens, tp_axis=tp_axis,
                fused=True)
            new, ok_rows = fused_head(x, lens + 1)
            if probe:
                # row-wise finite probe from the epilogue's streaming sweep
                # — boolean-identical to scanning the full logits row
                ok = ok & jnp.all(ok_rows | ~active)
        else:
            x, caches, _ = paged_decode_stack(arch, stacked, caches, x,
                                              page_table, lens,
                                              tp_axis=tp_axis)
            logits = unembed(x)
            new = select(logits, lens + 1)
            if probe:
                # inactive slots read the null page and may legitimately
                # produce junk — probe only the live rows
                ok = ok & jnp.all(jnp.isfinite(logits) | ~active[:, None])
        buf = buf.at[i].set(new)
        reasons = reasons \
            | jnp.where(active & (new == eos_ids), EXIT_EOS, 0) \
            | jnp.where(active & (i + 1 >= budget), EXIT_BUDGET, 0)
        lens = lens + active.astype(lens.dtype)
        return (i + 1, new, lens, caches, buf, reasons, ok)

    carry = (jnp.zeros((), jnp.int32), tokens, seq_lens, caches,
             jnp.zeros((horizon, n_slots), jnp.int32),
             jnp.zeros((n_slots,), jnp.int32), jnp.asarray(True))
    steps, _tok, lens, caches, buf, reasons, ok = jax.lax.while_loop(
        _cond, _body, carry)
    reasons = reasons | jnp.where(active & (lens >= page_limit),
                                  EXIT_PAGES, 0)
    if probe:
        return buf, steps, reasons, caches, ok
    return buf, steps, reasons, caches


def paged_prefill_period(arch: ArchConfig, p: PyTree, cache: PyTree,
                         x: jax.Array, page_row: jax.Array, start: jax.Array,
                         total_len: jax.Array, slot: jax.Array,
                         moe_cap: Optional[jax.Array] = None,
                         mrope_positions=None,
                         tp_axis: Optional[str] = None,
                         fused: bool = False) -> Tuple[jax.Array, PyTree]:
    if fused:
        assert not arch.post_norm, (arch.name, "fused prefill is pre-norm only")
    new_cache: PyTree = {}
    d: Optional[jax.Array] = None     # pending in-period residual delta
    # MoE capacity for a prompt chunk: the FULL context's bucket (computed
    # host-side by the engine with the same math as the static path), not
    # the padded chunk shape's. The trailing padding itself is harmless —
    # the stable expert sort keeps padded entries behind every real token —
    # but the chunk shape would otherwise inflate the drop threshold away
    # from the static engine's, so a prompt that fits one chunk drops
    # exactly what a full-prompt dispatch would. Longer prompts still
    # re-bucket per chunk (documented caveat).
    moe_eff_cap = moe_cap if arch.moe is not None else None
    # the chunk's padding past the prompt routes nowhere
    rows = (start + jnp.arange(x.shape[1]) < total_len)[None]
    for i, (mixer, _) in enumerate(layer_kinds(arch)):
        x = constrain(x, "batch", None, None)
        blk = p[f"layer_{i}"]

        def mix(h, blk=blk, i=i, mixer=mixer):
            if mixer == "attn":
                return attn_lib.paged_prefill_attention_layer(
                    arch, blk["attn"], h, cache[f"layer_{i}"], page_row,
                    start, total_len, mrope_positions, tp_axis)
            return ssm_lib.paged_prefill_mamba_layer(
                arch, blk["mamba"], h, cache[f"layer_{i}"], slot, start,
                total_len)
        if fused:
            if d is None:
                h = apply_norm(arch.norm, blk["ln1"], x, arch.norm_eps)
            else:
                h, x = _fused_residual_norm(arch, blk["ln1"], d, x)
            y, new_cache[f"layer_{i}"] = mix(h)
            if arch.family == "ssm":
                d = y
            else:
                h2, x = _fused_residual_norm(arch, blk["ln2"], y, x)
                d, _ = _fused_block_delta(arch, blk, h2, tp_axis, moe_eff_cap,
                                          rows)
        else:
            x, new_cache[f"layer_{i}"] = _decode_block_mix(arch, blk, x, mix)
            x, _ = _decode_block_ffn(arch, blk, x, tp_axis,
                                     moe_eff_cap=moe_eff_cap, rows=rows)
    if fused:
        x = x + d
    return x, new_cache


def chunk_final_hidden(x: jax.Array, start: jax.Array,
                       total_len: jax.Array) -> jax.Array:
    """[B, C, D] chunk activations -> [B, 1, D] hidden state of the chunk's
    last *valid* token (position ``total_len - 1``; the chunk is padded past
    it). This is the logits surface for the final prefill chunk: the LM head
    + sampler run on exactly this one position — earlier chunks exist only
    to fill KV pages and never pay the head."""
    return jax.lax.dynamic_slice_in_dim(x, total_len - 1 - start, 1, axis=1)


def paged_prefill_stack(arch: ArchConfig, stacked: PyTree, caches: PyTree,
                        x: jax.Array, page_row: jax.Array, start: jax.Array,
                        total_len: jax.Array, slot: jax.Array = None,
                        moe_cap: Optional[jax.Array] = None,
                        mrope_positions=None,
                        tp_axis: Optional[str] = None,
                        fused: bool = False):
    """Chunked prefill: one prompt chunk [1, C, D] of one sequence through
    the stack — attention K/V written straight into the sequence's pages,
    mamba state advanced in the sequence's slot row (``slot``; only needed
    for SSM-bearing stacks), MoE layers dropping at the full context's
    capacity (``moe_cap``, host-computed; only read for MoE-bearing
    stacks). The caller slices the sampling position out of the returned
    activations with ``chunk_final_hidden``. ``fused=True`` mirrors
    ``paged_decode_stack``: residual+norm-fused period bodies, plain
    completed residual as the carry, bit-identical to ``fused=False``."""
    if slot is None:
        slot = jnp.zeros((), jnp.int32)
    if fused:
        assert not arch.post_norm, (arch.name, "fused prefill is pre-norm only")
    if lead_layers(arch):
        lead, main = split_lead(arch)
        x, lc = paged_prefill_stack(lead, stacked["lead"], caches["lead"], x,
                                    page_row, start, total_len, slot, moe_cap,
                                    mrope_positions, tp_axis, fused)
        x, mc = paged_prefill_stack(main, stacked["main"], caches["main"], x,
                                    page_row, start, total_len, slot, moe_cap,
                                    mrope_positions, tp_axis, fused)
        return x, {"lead": lc, "main": mc}
    if isinstance(stacked, dict) and any(k.startswith("period_") for k in stacked):
        new_caches: PyTree = {}
        for z in range(len(stacked)):
            x, nc = paged_prefill_period(
                arch, stacked[f"period_{z}"], caches[f"period_{z}"],
                x, page_row, start, total_len, slot,
                moe_cap, mrope_positions, tp_axis, fused=fused)
            new_caches[f"period_{z}"] = nc
        return x, new_caches

    def scan_body(h, inputs):
        period_params, cache = inputs
        h, new_cache = paged_prefill_period(
            arch, period_params, cache, h, page_row,
            start, total_len, slot, moe_cap, mrope_positions, tp_axis,
            fused=fused)
        return h, new_cache
    x, new_caches = jax.lax.scan(scan_body, x, (stacked, caches))
    return x, new_caches


def decode_period(arch: ArchConfig, p: PyTree, cache: PyTree, x: jax.Array,
                  positions: jax.Array, mrope_positions=None
                  ) -> Tuple[jax.Array, PyTree]:
    new_cache: PyTree = {}
    for i, (mixer, _) in enumerate(layer_kinds(arch)):
        x = constrain(x, "batch", None, None)
        blk = p[f"layer_{i}"]
        layer_cache = cache[f"layer_{i}"]

        def mix(h, blk=blk, layer_cache=layer_cache, mixer=mixer):
            if mixer == "attn":
                kv_cache = {"k": layer_cache["k"], "v": layer_cache["v"]}
                y, new_kv = attn_lib.extend_attention(
                    arch, blk["attn"], h, kv_cache, positions, mrope_positions)
                new_c = dict(layer_cache)
                new_c.update(new_kv)
                return y, new_c
            return ssm_lib.extend_mamba(arch, blk["mamba"], h, layer_cache)
        x, new_cache[f"layer_{i}"] = _decode_block_mix(arch, blk, x, mix)

        if "xattn" in blk:
            h = apply_norm(arch.norm, blk["ln_x"], x, arch.norm_eps)
            enc_kv = (layer_cache["cross_k"], layer_cache["cross_v"])
            x = x + attn_lib.apply_cross_attention(arch, blk["xattn"], h, enc_kv)

        x, _ = _decode_block_ffn(arch, blk, x)
    return x, new_cache


def decode_stack(arch: ArchConfig, stacked: PyTree, caches: PyTree, x: jax.Array,
                 positions: jax.Array, mrope_positions=None
                 ) -> Tuple[jax.Array, PyTree]:
    if lead_layers(arch):
        lead, main = split_lead(arch)
        x, lc = decode_stack(lead, stacked["lead"], caches["lead"], x,
                             positions, mrope_positions)
        x, mc = decode_stack(main, stacked["main"], caches["main"], x,
                             positions, mrope_positions)
        return x, {"lead": lc, "main": mc}
    if isinstance(stacked, dict) and any(k.startswith("period_") for k in stacked):
        new_caches: PyTree = {}
        for z in range(len(stacked)):
            x, nc = decode_period(arch, stacked[f"period_{z}"],
                                  caches[f"period_{z}"], x, positions,
                                  mrope_positions)
            new_caches[f"period_{z}"] = nc
        return x, new_caches

    def scan_body(h, inputs):
        period_params, cache = inputs
        h, new_cache = decode_period(arch, period_params, cache, h,
                                     positions, mrope_positions)
        return h, new_cache
    x, new_caches = jax.lax.scan(scan_body, x, (stacked, caches))
    return x, new_caches
