"""GQA attention: fused/serial QKV projections, naive + chunked (online-softmax)
implementations, KV-cache decode path, RoPE / M-RoPE, optional sliding window.

The paper's Fig 14/15 "GEMM fusion" optimization is the ``fuse_qkv`` init/apply
option: one [D, (Hq+2*Hkv)*Dh] GEMM instead of three. The paper's memory-bound
"attention B-GEMM + scale/mask/softmax" ops (§3.2.3) are what the chunked/flash
implementations restructure for TPU: no [S, S] score tensor is ever resident in HBM —
the online-softmax recurrence keeps a [Sq, chunk] tile in VMEM (Pallas kernel in
``repro.kernels.flash_attention``; the pure-JAX chunked path here is its oracle and
the CPU-lowerable stand-in used by the dry-run).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..parallel.sharding import constrain
from .layers import PyTree, apply_mrope, apply_rope, dense, dense_init

NEG_INF = -1e30


# ------------------------------------------------------------------------- init ---

def init_attention(key, arch: ArchConfig, fuse_qkv: bool = True,
                   cross: bool = False, dtype=jnp.float32) -> PyTree:
    d, hd = arch.d_model, arch.resolved_head_dim
    qd, kvd = arch.q_dim, arch.kv_dim
    ks = jax.random.split(key, 4)
    p: PyTree = {}
    if fuse_qkv and not cross:
        p["wqkv"] = dense_init(ks[0], d, qd + 2 * kvd, dtype)
        if arch.use_bias:
            p["bqkv"] = jnp.zeros((qd + 2 * kvd,), dtype)
    else:
        p["wq"] = dense_init(ks[0], d, qd, dtype)
        p["wk"] = dense_init(ks[1], d, kvd, dtype)
        p["wv"] = dense_init(ks[2], d, kvd, dtype)
        if arch.use_bias:
            p["bq"] = jnp.zeros((qd,), dtype)
            p["bk"] = jnp.zeros((kvd,), dtype)
            p["bv"] = jnp.zeros((kvd,), dtype)
    p["wo"] = dense_init(ks[3], qd, d, dtype)
    if arch.use_bias:
        p["bo"] = jnp.zeros((d,), dtype)
    return p


def qkv_project(arch: ArchConfig, p: PyTree, x: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> q [B,S,Hq,Dh], k/v [B,S,Hkv,Dh].

    Head counts are inferred from the projection widths, not the arch: under
    the serving engine's tensor parallelism this runs inside shard_map on
    weight shards holding Hq/tp (resp. Hkv/tp) contiguous heads, and the
    reshape must follow the local width."""
    b, s, _ = x.shape
    hd = arch.resolved_head_dim
    if "wqkv" in p:
        qkv = dense(x, p["wqkv"], p.get("bqkv"))
        q, k, v = jnp.split(qkv, [arch.q_dim, arch.q_dim + arch.kv_dim], axis=-1)
    else:
        q = dense(x, p["wq"], p.get("bq"))
        k = dense(x, p["wk"], p.get("bk"))
        v = dense(x, p["wv"], p.get("bv"))
    q = q.reshape(b, s, q.shape[-1] // hd, hd)
    k = k.reshape(b, s, k.shape[-1] // hd, hd)
    v = v.reshape(b, s, v.shape[-1] // hd, hd)
    return q, k, v


def position_encode(arch: ArchConfig, q: jax.Array, k: jax.Array,
                    positions: jax.Array,
                    mrope_positions: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    if arch.pos_emb == "rope":
        q = apply_rope(q, positions, arch.rope_theta)
        k = apply_rope(k, positions, arch.rope_theta)
    elif arch.pos_emb == "mrope":
        if mrope_positions is None:
            # text-only fallback: t == h == w == position
            mrope_positions = jnp.broadcast_to(positions[None],
                                               (3,) + positions.shape)
        q = apply_mrope(q, mrope_positions, arch.rope_theta)
        k = apply_mrope(k, mrope_positions, arch.rope_theta)
    # learned / sinusoidal / none: applied at the embedding, nothing to do here
    return q, k


# ------------------------------------------------------------ core implementations

def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q [B,Sq,Hq,D], k [B,Sk,Hkv,D] -> scores [B,Hq,Sq,Sk] with GQA grouping."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k)
    return s.reshape(b, hq, sq, k.shape[1])


def _gqa_values(p: jax.Array, v: jax.Array) -> jax.Array:
    """p [B,Hq,Sq,Sk], v [B,Sk,Hkv,D] -> [B,Sq,Hq,D]."""
    b, hq, sq, sk = p.shape
    hkv = v.shape[2]
    g = hq // hkv
    pg = p.reshape(b, hkv, g, sq, sk)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pg, v)
    return o.reshape(b, sq, hq, v.shape[3])


def naive_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, q_offset: int = 0,
                    kv_len: Optional[jax.Array] = None,
                    window: int = 0) -> jax.Array:
    """Reference full-matrix attention (test/small shapes; the chunked oracle)."""
    d = q.shape[-1]
    s = _gqa_scores(q, k).astype(jnp.float32) / jnp.sqrt(d).astype(jnp.float32)
    sq, sk = s.shape[2], s.shape[3]
    rows = jnp.arange(sq)[:, None] + q_offset
    cols = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= cols > rows - window
    s = jnp.where(mask[None, None], s, NEG_INF)
    if kv_len is not None:  # per-batch valid cache length: [B]
        valid = cols[None] < kv_len[:, None, None]          # [B,1,Sk]
        s = jnp.where(valid[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return _gqa_values(p, v)


def _chunk_mask(sq: int, chunk: int, j, *, causal: bool, q_offset: int,
                window: int, kv_len, scores: jax.Array) -> jax.Array:
    """Apply causal/window/cache-length masking to one [B,Hq,Sq,chunk] tile."""
    rows = jnp.arange(sq)[:, None] + q_offset
    cols = j * chunk + jnp.arange(chunk)[None, :]
    mask = jnp.ones((sq, chunk), bool)
    if causal:
        mask &= cols <= rows
    if window > 0:
        mask &= cols > rows - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    if kv_len is not None:
        valid = cols[None] < kv_len[:, None, None]
        scores = jnp.where(valid[:, None], scores, NEG_INF)
    return scores


def _chunked_fwd_impl(q, k, v, kv_len, causal, chunk, q_offset, window):
    b, sq, hq, d = q.shape
    nchunks = k.shape[1] // chunk
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    k_ch = k.reshape(b, nchunks, chunk, *k.shape[2:]).transpose(1, 0, 2, 3, 4)
    v_ch = v.reshape(b, nchunks, chunk, *v.shape[2:]).transpose(1, 0, 2, 3, 4)

    def body(carry, inputs):
        o, m, l = carry                                      # [B,Hq,Sq,D] fp32 acc
        j, kj, vj = inputs
        s = _gqa_scores(q, kj).astype(jnp.float32) * scale   # [B,Hq,Sq,chunk]
        s = _chunk_mask(sq, chunk, j, causal=causal, q_offset=q_offset,
                        window=window, kv_len=kv_len, scores=s)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))          # [B,Hq,Sq]
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])                    # [B,Hq,Sq,chunk]
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = _gqa_values(p.astype(q.dtype), vj)              # [B,Sq,Hq,D]
        pv = pv.transpose(0, 2, 1, 3).astype(jnp.float32)    # [B,Hq,Sq,D]
        o_new = o * alpha[..., None] + pv
        return (o_new, m_new, l_new), None

    o0 = jnp.zeros((b, hq, sq, d), jnp.float32)
    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    (o, m, l), _ = jax.lax.scan(
        body, (o0, m0, l0), (jnp.arange(nchunks), k_ch, v_ch))
    l = jnp.maximum(l, 1e-30)
    out = (o / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
    lse = m + jnp.log(l)                                     # [B,Hq,Sq]
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _chunked_attn(q, k, v, kv_len, causal, chunk, q_offset, window):
    out, _ = _chunked_fwd_impl(q, k, v, kv_len, causal, chunk, q_offset, window)
    return out


def _chunked_attn_fwd(q, k, v, kv_len, causal, chunk, q_offset, window):
    out, lse = _chunked_fwd_impl(q, k, v, kv_len, causal, chunk, q_offset,
                                 window)
    return out, (q, k, v, kv_len, out, lse)


def _chunked_attn_bwd(causal, chunk, q_offset, window, res, do):
    """Flash-attention backward: recompute score tiles per chunk, never holding
    more than one [B,Hq,Sq,chunk] tile (the per-chunk saves of plain autodiff
    through the forward scan cost GBs/layer — see EXPERIMENTS.md §Perf)."""
    q, k, v, kv_len, out, lse = res
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    nchunks = k.shape[1] // chunk
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    k_ch = k.reshape(b, nchunks, chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    v_ch = v.reshape(b, nchunks, chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    do_g = do.astype(jnp.float32).reshape(b, sq, hkv, g, d)
    out_f = out.astype(jnp.float32)
    delta = jnp.sum(do.astype(jnp.float32) * out_f, axis=-1)  # [B,Sq,Hq]
    delta = delta.transpose(0, 2, 1)                          # [B,Hq,Sq]
    qf = q.astype(jnp.float32).reshape(b, sq, hkv, g, d)

    def body(dq_acc, inputs):
        j, kj, vj = inputs
        s = _gqa_scores(q, kj).astype(jnp.float32) * scale    # [B,Hq,Sq,C]
        s = _chunk_mask(sq, chunk, j, causal=causal, q_offset=q_offset,
                        window=window, kv_len=kv_len, scores=s)
        p = jnp.exp(s - lse[..., None])                       # [B,Hq,Sq,C]
        pg = p.reshape(b, hkv, g, sq, chunk)
        kjf = kj.astype(jnp.float32)
        vjf = vj.astype(jnp.float32)
        dv_j = jnp.einsum("bhgqc,bqhgd->bchd", pg, do_g)      # [B,C,Hkv,D]
        dp = jnp.einsum("bqhgd,bchd->bhgqc", do_g, vjf)
        ds = pg * (dp - delta.reshape(b, hkv, g, sq)[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhgqc,bchd->bqhgd", ds, kjf)
        dk_j = jnp.einsum("bhgqc,bqhgd->bchd", ds, qf)
        return dq_acc, (dk_j, dv_j)

    dq0 = jnp.zeros((b, sq, hkv, g, d), jnp.float32)
    dq, (dk_ch, dv_ch) = jax.lax.scan(
        body, dq0, (jnp.arange(nchunks), k_ch, v_ch))
    dq = dq.reshape(b, sq, hq, d).astype(q.dtype)
    dk = dk_ch.transpose(1, 0, 2, 3, 4).reshape(b, nchunks * chunk, hkv, d)
    dv = dv_ch.transpose(1, 0, 2, 3, 4).reshape(b, nchunks * chunk, hkv, d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None


_chunked_attn.defvjp(_chunked_attn_fwd, _chunked_attn_bwd)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, chunk: int, q_offset: int = 0,
                      kv_len: Optional[jax.Array] = None,
                      window: int = 0) -> jax.Array:
    """Online-softmax attention over KV chunks with a flash-style custom VJP.

    Never materializes [Sq, Sk] in either direction; peak live score tile is
    [B, Hq, Sq, chunk]. This is the lowerable stand-in (and the oracle) for the
    Pallas flash kernel.
    """
    b = q.shape[0]
    sk = k.shape[1]
    if sk % chunk != 0:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        tail_len = jnp.full((b,), sk, jnp.int32)
        kv_len = tail_len if kv_len is None else jnp.minimum(kv_len, tail_len)
    return _chunked_attn(q, k, v, kv_len, causal, chunk, q_offset, window)


def attention_core(arch: ArchConfig, q, k, v, *, causal: bool,
                   q_offset: int = 0, kv_len=None) -> jax.Array:
    impl = arch.attn_impl
    kwargs = dict(causal=causal, q_offset=q_offset, kv_len=kv_len,
                  window=arch.window)
    if impl == "naive" or k.shape[1] <= arch.attn_chunk or q.shape[1] == 1:
        # single-query decode stays on the un-chunked path: with the KV cache
        # sharded on its length axis the only collectives are [B,H,1] softmax
        # stats + the [B,H,D] output reduction (see parallel/sharding.py).
        return naive_attention(q, k, v, **kwargs)
    if impl in ("chunked", "flash"):
        # "flash" lowers to the Pallas kernel on TPU backends; its CPU/dry-run
        # stand-in is the chunked path (same dataflow at HBM granularity).
        if impl == "flash":
            from ..kernels.flash_attention import ops as flash_ops
            if flash_ops.supported():
                return flash_ops.flash_attention(
                    q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
                    window=arch.window, block_kv=arch.attn_chunk)
        return chunked_attention(q, k, v, chunk=arch.attn_chunk, **kwargs)
    raise ValueError(impl)


# --------------------------------------------------------------- full layer apply -

def apply_attention(arch: ArchConfig, p: PyTree, x: jax.Array,
                    positions: jax.Array, *, causal: bool = True,
                    mrope_positions=None) -> jax.Array:
    """Training/prefill self-attention over the full sequence."""
    b, s, _ = x.shape
    with jax.named_scope("attn_qkv"):
        q, k, v = qkv_project(arch, p, x)
        q, k = position_encode(arch, q, k, positions, mrope_positions)
    # context-parallel attention: query-seq dim sharded on model (always even,
    # unlike head counts — qwen2's 12 heads over 16 devices would churn
    # collective-permutes); k/v replicated over model within the microbatch.
    q = constrain(q, "batch", "seq", None, None)
    k = constrain(k, "batch", None, None, None)
    v = constrain(v, "batch", None, None, None)
    with jax.named_scope("attn_core"):
        o = attention_core(arch, q, k, v, causal=causal)
        o = constrain(o, "batch", "seq", None, None)
    with jax.named_scope("attn_out"):
        o = o.reshape(b, s, arch.q_dim)
        return dense(o, p["wo"], p.get("bo"))


def apply_cross_attention(arch: ArchConfig, p: PyTree, x: jax.Array,
                          enc_kv: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """Whisper-style cross attention; enc k/v precomputed [B,Senc,Hkv,Dh]."""
    b, s, _ = x.shape
    hd = arch.resolved_head_dim
    q = dense(x, p["wq"], p.get("bq")).reshape(b, s, arch.num_heads, hd)
    k, v = enc_kv
    o = attention_core(arch, q, k, v, causal=False)
    return dense(o.reshape(b, s, arch.q_dim), p["wo"], p.get("bo"))


def project_enc_kv(arch: ArchConfig, p: PyTree, enc_out: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    b, s, _ = enc_out.shape
    hd = arch.resolved_head_dim
    k = dense(enc_out, p["wk"], p.get("bk")).reshape(b, s, arch.num_kv_heads, hd)
    v = dense(enc_out, p["wv"], p.get("bv")).reshape(b, s, arch.num_kv_heads, hd)
    return k, v


# ------------------------------------------------------------------- decode path --

def init_kv_cache(arch: ArchConfig, batch: int, max_len: int, dtype) -> PyTree:
    hd = arch.resolved_head_dim
    shape = (batch, max_len, arch.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _update_cache_row(cache_row: jax.Array, new_rows: jax.Array,
                      pos: jax.Array) -> jax.Array:
    # cache_row [Smax, Hkv, D]; new_rows [S, Hkv, D]
    return jax.lax.dynamic_update_slice(cache_row, new_rows, (pos, 0, 0))


def extend_attention(arch: ArchConfig, p: PyTree, x: jax.Array,
                     cache: PyTree, positions: jax.Array,
                     mrope_positions=None) -> Tuple[jax.Array, PyTree]:
    """Attend S new tokens against (and into) a KV cache.

    x [B,S,D]; positions [B] = first cache index for the new tokens. S == 1 is
    decode; S > 1 with positions == 0 is prefill (causal among the new tokens).
    """
    b, s, _ = x.shape
    q, k, v = qkv_project(arch, p, x)                        # [B,S,H*,D]
    qpos = positions[:, None] + jnp.arange(s)[None, :]       # [B,S]
    q, k = position_encode(arch, q, k, qpos, mrope_positions)
    if s > 1:
        q = constrain(q, "batch", "seq", None, None)
    else:
        q = constrain(q, "batch", None, None, None)
    k = constrain(k, "batch", None, None, None)
    v = constrain(v, "batch", None, None, None)
    new_k = jax.vmap(_update_cache_row)(cache["k"], k, positions)
    new_v = jax.vmap(_update_cache_row)(cache["v"], v, positions)
    if s > 1:
        # prefill (positions == 0 by construction): attend over the fresh K/V —
        # fully local under activation sharding; the cache write above is the
        # one-time [seq->model] cache-layout reshard.
        o = attention_core(arch, q, k, v, causal=True)
    else:
        kv_len = positions + s
        o = attention_core(arch, q, new_k, new_v, causal=False, kv_len=kv_len)
    o = o.reshape(b, s, arch.q_dim)
    y = dense(o, p["wo"], p.get("bo"))
    return y, {"k": new_k, "v": new_v}


def decode_attention(arch: ArchConfig, p: PyTree, x: jax.Array,
                     cache: PyTree, positions: jax.Array,
                     mrope_positions=None) -> Tuple[jax.Array, PyTree]:
    """One-token decode. x [B,1,D]; positions [B] (current index into the cache)."""
    return extend_attention(arch, p, x, cache, positions, mrope_positions)


# ------------------------------------------------------------- paged decode path --

def init_paged_kv_cache(arch: ArchConfig, num_pages: int, page_size: int,
                        dtype) -> PyTree:
    """Global page pool for one attention layer. Page 0 is the null page:
    never allocated to a sequence, it absorbs writes from inactive slots and
    padded page-table entries."""
    hd = arch.resolved_head_dim
    shape = (num_pages, page_size, arch.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _row_parallel_out(p: PyTree, o: jax.Array, x_dtype,
                      tp_axis: Optional[str]) -> jax.Array:
    """Output projection of a paged attention layer.

    Single-device: the plain dense. Under serving TP (inside shard_map) the
    shard's ``wo`` rows cover only its local heads, so the GEMM yields a
    partial sum — psum it over the axis in fp32 and add the (replicated)
    bias once, after the reduce.
    """
    if tp_axis is None:
        return dense(o, p["wo"], p.get("bo"))
    y = o.astype(jnp.float32) @ p["wo"].astype(jnp.float32)
    y = jax.lax.psum(y, tp_axis)
    if "bo" in p:
        y = y + p["bo"].astype(jnp.float32)
    return y.astype(x_dtype)


def paged_prefill_attention_layer(arch: ArchConfig, p: PyTree, x: jax.Array,
                                  cache: PyTree, page_row: jax.Array,
                                  start: jax.Array, total_len: jax.Array,
                                  mrope_positions=None,
                                  tp_axis: Optional[str] = None
                                  ) -> Tuple[jax.Array, PyTree]:
    """One prompt chunk of a single sequence, written directly into its pages.

    x [1, C, D] — chunk token embeddings (row i at absolute position
    start + i); page_row [max_pages] (this sequence's page-table row);
    start = tokens already cached; total_len = start + valid tokens in the
    chunk (the rest of the chunk is padding). K/V rows land straight in the
    page pool — no dense bucket cache, no scatter pass — and padding rows
    (or rows past the allocated pages) are routed to the null page 0.

    With ``tp_axis`` set this body runs per shard: local q/k/v heads, the
    shard's slice of the page pool, and a row-parallel output projection
    psum'd over the axis — the layer's only collective.
    """
    b, c, _ = x.shape
    assert b == 1, "chunked prefill runs one sequence at a time"
    page_size = cache["k"].shape[1]
    max_pages = page_row.shape[0]
    q, k, v = qkv_project(arch, p, x)                        # [1,C,H*,D]
    pos = jnp.asarray(start, jnp.int32) + jnp.arange(c, dtype=jnp.int32)
    q, k = position_encode(arch, q, k, pos[None], mrope_positions)
    logical = pos // page_size
    valid = (pos < total_len) & (logical < max_pages)
    pids = jnp.where(valid,
                     page_row[jnp.clip(logical, 0, max_pages - 1)], 0)
    offs = pos % page_size
    new_k = cache["k"].at[pids, offs].set(k[0])
    new_v = cache["v"].at[pids, offs].set(v[0])
    from ..kernels.decode_attention import ops as pd_ops
    o = pd_ops.paged_prefill_attention(q[0], new_k, new_v, page_row, start,
                                       total_len)
    y = _row_parallel_out(p, o.reshape(1, c, -1), x.dtype, tp_axis)
    return y, {"k": new_k, "v": new_v}


def paged_decode_attention_layer(arch: ArchConfig, p: PyTree, x: jax.Array,
                                 cache: PyTree, page_table: jax.Array,
                                 seq_lens: jax.Array,
                                 mrope_positions=None,
                                 tp_axis: Optional[str] = None,
                                 layer: Optional[jax.Array] = None
                                 ) -> Tuple[jax.Array, PyTree]:
    """One-token decode against a paged KV cache.

    x [B,1,D]; cache k/v [P, page, Hkv, D]; page_table [B, max_pages];
    seq_lens [B] = tokens already in the cache (the new token's position).
    Inactive slots carry seq_len 0: their K/V lands in the null page and
    their attention output is garbage the engine never reads.

    With ``layer`` (a scalar) the cache is a layer scan's whole stack
    [L, P, page, Hkv, D] and this layer's pool is its ``layer``-th: the
    step's rows are scattered at ``[layer, page, offset]`` and the kernel
    reads the stack at ``layer``, so no pool is sliced out of the stack or
    written back into it (the scan carries the stack, and XLA keeps the
    scatter in place on it).

    With ``tp_axis`` set this body runs per shard_map shard (Megatron head
    parallelism): the weight shards project only the local Hq/tp query and
    Hkv/tp KV heads, the cache shard is the local heads' slice of every
    page, and the row-parallel output projection is psum'd over the axis —
    attention's single collective per layer.
    """
    b, s, _ = x.shape
    assert s == 1, "paged path is single-query decode only"
    page_size = cache["k"].shape[-3]
    q, k, v = qkv_project(arch, p, x)                        # [B,1,H*,D]
    q, k = position_encode(arch, q, k, seq_lens[:, None], mrope_positions)
    pids = page_table[jnp.arange(b), seq_lens // page_size]  # [B]
    offs = seq_lens % page_size
    at = (pids, offs) if layer is None else (layer, pids, offs)
    new_k = cache["k"].at[at].set(k[:, 0])
    new_v = cache["v"].at[at].set(v[:, 0])
    from ..kernels.decode_attention import ops as pd_ops
    o = pd_ops.paged_decode_attention(q[:, 0], new_k, new_v, page_table,
                                      seq_lens + 1,
                                      0 if layer is None else layer)
    y = _row_parallel_out(p, o.reshape(b, 1, -1), x.dtype, tp_axis)
    return y, {"k": new_k, "v": new_v}
