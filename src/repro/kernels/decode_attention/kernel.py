"""Pallas TPU paged decode-attention (single query per sequence, GQA).

The serving regime the paper's §3.2.3 measurements predict to be memory-bound:
at decode the attention "B-GEMMs" degenerate to matrix-vector products, so
runtime is the HBM read of the KV cache itself. With a *paged* cache the K/V
rows of one sequence are scattered across fixed-size pages of a global pool;
this kernel gathers them with its own DMAs through a scalar-prefetched page
table instead of a materialized [B, L, H, D] gather in HBM.

Layout: q [B, Hq, D]; k/v pools [L, P, page_size, Hkv, D], the stacked
pools of L layers (one pool [P, page_size, Hkv, D] is L = 1), viewed as
[L, P, page_size * Hkv, D] (token-major rows), which stay in HBM
(``memory_space=pl.ANY``): one page of every KV head is one contiguous
slab. On a TPU this view keeps the pool's own (Hkv, D) tiling, so it
compiles to a bitcast, where the [P, page_size, Hkv * D] view the prefill
kernel takes is a copy of the whole pool. A scalar-prefetched layer index
picks the pool a page is copied from, so the model step hands the kernel
its whole stack and no layer's pool is ever sliced out of it.
page_table [B, max_pages]; seq_lens [B].

Walk: grid (B,), one sequence a step. An in-kernel loop takes the
sequence's ``ceil(seq_len / block)`` blocks of ``block = pages_per_block *
page_size`` tokens and nothing past them; a slot with seq_len 0 issues no
DMA and writes zeros. A block is gathered into VMEM with one DMA per live
page for K and one for V, indexed through the page table, and
double-buffered: block i+1's copies (or the next sequence's first block's)
start before block i is computed. All Hq query heads meet all of the
block's rows in one [Hq, block * Hkv] score matrix, each keeping only its
own KV head's live rows (the others are masked out before the softmax, so
they add exact zeros), with fp32 online-softmax accumulators (acc, m, l)
per query head.

Block rule: ``ops.pages_per_block`` (shared with the engine's ``kv_blocks``
count) takes ``ops.BLOCK_TOKENS`` tokens a block, capped by ``max_pages``
and by a VMEM budget for the 2 (K, V) x 2 (buffers) blocks, so the block
follows ``page_size``, the local ``Hkv * D`` and ``max_pages``.

``_paged_prefill_kernel`` is the multi-query sibling used by chunked prefill:
one prompt chunk of C tokens (single sequence, grid (Hkv, max_pages)) attends
causally to the cached prefix plus itself through a scalar-prefetched page
walk in its BlockSpec index_map (one [page_size, D] tile per KV head and
page), with [C*G, D] accumulators — so prompt ingestion streams K/V tiles
instead of materializing a dense cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ops import pages_per_block

NEG_INF = -1e30


def _paged_decode_kernel(pt_ref, sl_ref, layer_ref, q_ref, lo_ref, k_hbm,
                         v_hbm, o_ref, k_buf, v_buf, sems, acc_ref, m_ref,
                         l_ref, slot_ref, *, page_size, pages_per_block,
                         group, scale):
    b = pl.program_id(0)
    n_seqs = pl.num_programs(0)
    hq, d = q_ref.shape
    max_pages = pt_ref.shape[1]
    layer = layer_ref[0]
    page_rows = k_hbm.shape[2]                  # page_size * Hkv
    hkv = page_rows // page_size
    block = pages_per_block * page_size         # tokens
    rows = pages_per_block * page_rows          # buffer rows: token, head

    def for_pages(seq, i, slot, act):
        """Calls ``act`` on the K and V copies of each live page of block
        ``i`` of ``seq`` into buffer ``slot``; pages at or past the live
        length are not copied (their rows are masked below)."""
        live_pages = jax.lax.min(pl.cdiv(sl_ref[seq], page_size), max_pages)
        first = i * pages_per_block

        def page(p, carry):
            src = pt_ref[seq, first + p]
            dst = pl.ds(pl.multiple_of(p * page_rows, page_rows), page_rows)
            for kv, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                             (v_hbm, v_buf))):
                act(pltpu.make_async_copy(hbm.at[layer, src],
                                          buf.at[slot, dst],
                                          sems.at[kv, slot]))
            return carry

        n = jax.lax.clamp(0, live_pages - first, pages_per_block)
        jax.lax.fori_loop(0, n, page, 0)

    def start(seq, i, slot):
        for_pages(seq, i, slot, lambda c: c.start())

    def wait(seq, i, slot):
        for_pages(seq, i, slot, lambda c: c.wait())

    sl = sl_ref[b]
    n_blocks = pl.cdiv(sl, block)
    has_next = b + 1 < n_seqs

    # the first sequence's first block; every later one is started by the
    # grid step before it, into the buffer ``slot_ref`` names
    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        start(b, 0, 0)

    slot0 = slot_ref[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[...].astype(jnp.float32) * scale                    # [Hq, D]
    # query head x belongs to row r's KV head iff lo[r] <= x < lo[r] + G
    head = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 0)
    lo = lo_ref[...]                                              # [1, R]
    mine = (head >= lo) & (head < lo + group)

    # lax.rem and lax.select, not % and jnp.where: those trace once and are
    # cached, and would carry another kernel's source names into this
    # one's Mosaic payload, where the benchmark looks up the kernel's name
    def step(i, carry):
        slot = jax.lax.rem(slot0 + i, 2)
        # block i+1, or the next sequence's first block, streams into the
        # other buffer while this one is computed
        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            start(b, i + 1, 1 - slot)

        @pl.when((i + 1 == n_blocks) & has_next)
        def _prefetch_next_seq():
            start(b + 1, 0, 1 - slot)

        wait(b, i, slot)
        # rows are token-major: the block's first live_rows hold its live
        # tokens; the rest hold another page's data or nothing
        live_rows = (sl - i * block) * hkv
        cols = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 1)
        k = k_buf[slot].astype(jnp.float32)                       # [R, D]
        v = v_buf[slot].astype(jnp.float32)
        # zero dead rows of V so that 0-weights cannot meet a NaN
        v = jax.lax.select(
            jax.lax.broadcasted_iota(jnp.int32, (rows, d), 0) < live_rows,
            v, jnp.zeros_like(v))
        # every query head against every row, each keeping its own KV
        # head's live rows
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [Hq, R]
        s = jax.lax.select(mine & (cols < live_rows), s,
                           jnp.full_like(s, NEG_INF))
        m_prev = m_ref[...]                                       # [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
        return carry

    jax.lax.fori_loop(0, n_blocks, step, 0)

    # an empty slot (sl == 0) walks nothing and writes zeros, but still
    # hands the next sequence its first block
    @pl.when((n_blocks == 0) & has_next)
    def _pass_on():
        start(b + 1, 0, slot0)

    slot_ref[0] = jax.lax.rem(slot0 + n_blocks, 2)
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


def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, seq_lens,
                               layer=0, *, interpret=False):
    """q [B, Hq, D]; k/v_pages [L, P, page, Hkv, D] (or one pool
    [P, page, Hkv, D], L = 1); page_table [B, max_pages]; seq_lens [B];
    layer: the pool of the stack to attend to, a scalar in [0, L)
    -> [B, Hq, D]. Decode is forward-only: no VJP."""
    if k_pages.ndim == 4:
        k_pages, v_pages = k_pages[None], v_pages[None]
    b, hq, d = q.shape
    n_layers, p, page_size, hkv, _ = k_pages.shape
    g = hq // hkv
    assert hq == g * hkv, (hq, hkv)
    max_pages = page_table.shape[1]
    ppb = pages_per_block(page_size, hkv * d * k_pages.dtype.itemsize,
                          max_pages)
    rows = ppb * page_size * hkv
    scale = 1.0 / (d ** 0.5)
    # [L, P, page * Hkv, D]: token-major rows, the pool's own tiled layout
    k_rows = k_pages.reshape(n_layers, p, page_size * hkv, d)
    v_rows = v_pages.reshape(n_layers, p, page_size * hkv, d)
    # lo[r]: the first query head of row r's KV head
    lo = jnp.asarray((np.arange(rows) % hkv * g)[None], jnp.int32)

    kern = functools.partial(_paged_decode_kernel, page_size=page_size,
                             pages_per_block=ppb, group=g, scale=scale)
    heads = pl.BlockSpec((None, hq, d), lambda bi, pt, sl, ly: (bi, 0, 0))
    whole = pl.BlockSpec((1, rows), lambda bi, pt, sl, ly: (0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, rows, d), k_pages.dtype)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[heads, whole, pool, pool],
            out_specs=heads,
            scratch_shapes=[
                buf, buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hq, d), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, d), q.dtype),
        # the buffers and the slot carry from one sequence to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(page_table.astype(jnp.int32), seq_lens.astype(jnp.int32),
      jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), q, lo, k_rows,
      v_rows)
