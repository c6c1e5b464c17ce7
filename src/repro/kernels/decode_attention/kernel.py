"""Pallas TPU paged decode-attention (single query per sequence, GQA).

The serving regime the paper's §3.2.3 measurements predict to be memory-bound:
at decode the attention "B-GEMMs" degenerate to matrix-vector products, so
runtime is the HBM read of the KV cache itself. With a *paged* cache the K/V
rows of one sequence are scattered across fixed-size pages of a global pool;
this kernel gathers them page-by-page through a scalar-prefetched page table,
so the gather happens in the BlockSpec index_map (pipelined HBM->VMEM DMAs)
instead of a materialized [B, L, H, D] gather in HBM.

Layout: q [B, Hkv, G, D] (G = Hq/Hkv query heads per KV head); k/v pools
[P, page_size, Hkv, D], viewed (a free reshape) as [P, page_size, Hkv * D]
so one KV head of one page is a [page_size, D] block the TPU tiling accepts
(D a multiple of 128); page_table [B, max_pages]; seq_lens [B]. Grid
(B, Hkv, max_pages): the page loop is the innermost grid dim, carrying fp32
online-softmax accumulators (acc, m, l) in VMEM scratch. Pages at or past
seq_len are skipped with ``pl.when`` (their table entries point at the null
page 0), so per-step work tracks the sequence's *actual* length, not max_len.

``_paged_prefill_kernel`` is the multi-query sibling used by chunked prefill:
one prompt chunk of C tokens (single sequence, grid (Hkv, max_pages)) attends
causally to the cached prefix plus itself through the same scalar-prefetched
page walk, with [C*G, D] accumulators — so prompt ingestion streams page-sized
K/V tiles exactly like decode instead of materializing a dense cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_decode_kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, page_size, scale):
    b = pl.program_id(0)
    j = pl.program_id(2)
    sl = sl_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # skip pages wholly past the end of the sequence (covers inactive slots,
    # sl == 0, whose rows stay zero after the final normalization)
    @pl.when(j * page_size < sl)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale            # [G, D]
        k = k_ref[...].astype(jnp.float32)                    # [page, D]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, page]
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page_size
        s = jnp.where(cols < sl, s, NEG_INF)
        m_prev = m_ref[...]                                   # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_prefill_kernel(pt_ref, meta_ref, q_ref, k_ref, v_ref, o_ref,
                          acc_ref, m_ref, l_ref, *, page_size, g, scale):
    j = pl.program_id(1)
    start = meta_ref[0]                 # tokens already cached (chunk offset)
    total = meta_ref[1]                 # valid cache length after this chunk

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a page contributes iff some valid token can see it: causality caps the
    # visible cache at the chunk's last valid position (total - 1)
    @pl.when(j * page_size < total)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale            # [C*G, D]
        k = k_ref[...].astype(jnp.float32)                    # [page, D]
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [C*G, page]
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * page_size
        # causal within the chunk (query i sits at position start + i) and
        # clipped to the valid cache; padding rows end up fully masked
        s = jnp.where((cols <= start + rows) & (cols < total), s, NEG_INF)
        m_prev = m_ref[...]                                   # [C*G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _pool_view(k_pages, v_pages, d, interpret):
    """[P, page, Hkv, D] pools as [P, page, Hkv * D] (free): a [page, D]
    block per (page, KV head) is tile-aligned when D is a multiple of 128
    (every servable config); interpret mode takes any D."""
    assert interpret or d % 128 == 0, f"head_dim {d} is not a multiple of 128"
    p, page_size, hkv, _ = k_pages.shape
    return (k_pages.reshape(p, page_size, hkv * d),
            v_pages.reshape(p, page_size, hkv * d))


def paged_prefill_attention_fwd(q, k_pages, v_pages, page_row, start,
                                total_len, *, interpret=False):
    """Chunked-prefill attention for ONE sequence against its paged cache.

    q [C, Hq, D] (the chunk's queries; row i sits at position start + i);
    k/v_pages [P, page, Hkv, D] — the chunk's K/V must already be written
    into the pages; page_row [max_pages]; start / total_len scalars with
    total_len = start + valid tokens in the chunk. -> [C, Hq, D]. Rows at or
    past total_len are padding: they attend to the valid prefix and return
    well-defined garbage the caller ignores.
    """
    c, hq, d = q.shape
    _, page_size, hkv, _ = k_pages.shape
    g = hq // hkv
    assert hq == g * hkv, (hq, hkv)
    max_pages = page_row.shape[0]
    scale = 1.0 / (d ** 0.5)
    k_view, v_view = _pool_view(k_pages, v_pages, d, interpret)

    # [Hkv, C*G, D]: row r of head h is query (r // G) of group member r % G
    qg = q.reshape(c, hkv, g, d).transpose(1, 0, 2, 3).reshape(hkv, c * g, d)
    pt = page_row.astype(jnp.int32)
    meta = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(total_len, jnp.int32)])

    kern = functools.partial(_paged_prefill_kernel, page_size=page_size,
                             g=g, scale=scale)
    rows = pl.BlockSpec((None, c * g, d), lambda h, j, pt, meta: (h, 0, 0))
    page = pl.BlockSpec((None, page_size, d),
                        lambda h, j, pt, meta: (pt[j], 0, h))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(hkv, max_pages),
            in_specs=[rows, page, page],
            out_specs=rows,
            scratch_shapes=[
                pltpu.VMEM((c * g, d), jnp.float32),
                pltpu.VMEM((c * g, 1), jnp.float32),
                pltpu.VMEM((c * g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((hkv, c * g, d), q.dtype),
        name="paged_prefill_attention",
        interpret=interpret,
    )(pt, meta, qg, k_view, v_view)
    return out.reshape(hkv, c, g, d).transpose(1, 0, 2, 3).reshape(c, hq, d)


def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, seq_lens, *,
                               interpret=False):
    """q [B, Hq, D]; k/v_pages [P, page, Hkv, D]; page_table [B, max_pages];
    seq_lens [B] -> [B, Hq, D]. Decode is forward-only: no VJP."""
    b, hq, d = q.shape
    _, page_size, hkv, _ = k_pages.shape
    g = hq // hkv
    assert hq == g * hkv, (hq, hkv)
    max_pages = page_table.shape[1]
    scale = 1.0 / (d ** 0.5)
    k_view, v_view = _pool_view(k_pages, v_pages, d, interpret)

    qg = q.reshape(b, hkv, g, d)
    pt = page_table.astype(jnp.int32)
    sl = seq_lens.astype(jnp.int32)

    kern = functools.partial(_paged_decode_kernel, page_size=page_size,
                             scale=scale)
    heads = pl.BlockSpec((None, None, g, d),
                         lambda bi, h, j, pt, sl: (bi, h, 0, 0))
    page = pl.BlockSpec((None, page_size, d),
                        lambda bi, h, j, pt, sl: (pt[bi, j], 0, h))
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, max_pages),
            in_specs=[heads, page, page],
            out_specs=heads,
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        name="paged_decode_attention",
        interpret=interpret,
    )(pt, sl, qg, k_view, v_view)
    return out.reshape(b, hq, d)
