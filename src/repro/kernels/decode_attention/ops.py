"""Dispatch wrapper for paged decode-attention.

The Pallas kernel lowers on TPU backends (and everywhere under
``interpret=True``, which is how the parity tests run it); CPU serving and the
dry-run fall back to the pure-JAX gather in ``ref.py`` — identical numerics to
the static engine's dense decode path.

Head counts are whatever the caller's arrays carry, NOT an arch contract:
under the serving engine's tensor parallelism these wrappers run inside
shard_map, where ``Hq``/``Hkv`` are the *local* head counts (arch counts
divided by tp) and the page pools are the shard's heads' slice of every
physical page. The only invariant is GQA consistency, Hq % Hkv == 0 — which
head sharding preserves because tp divides both counts.
"""
from __future__ import annotations

import jax

# Tokens of keys (and of values) one block of the paged decode walk
# gathers. On a TPU v5e at internlm2-1.8b widths (8 KV heads of 128, 16-token
# pages, 16 slots of 128 pages, 24 layer pools sliced per layer as the model
# step did before it handed the kernel the whole stack; these timings date
# from then), the kernel added 46 / 43 us a layer to the slice's own 303 at
# 256 / 512 tokens a block with live lengths of 250-700, and 158 / 137 us
# at full 2048-token contexts; smaller blocks were slower still (a walk
# that sliced K/V per head took 196 / 119 us alone at 64 / 128 tokens
# against 97 at 512): fewer, larger blocks pay less fixed cost a block
# than they lose to the unused tail of the last one.
BLOCK_TOKENS = 512
# VMEM the decode walk's K and V buffers may take, two blocks of each
# (BLOCK_TOKENS at those widths in bf16 takes all of it)
BLOCK_BUFFER_BYTES = 4 << 20


def supported() -> bool:
    return jax.default_backend() == "tpu"


def pages_per_block(page_size: int, row_bytes: int, max_pages: int) -> int:
    """Pages one block of the paged decode walk gathers, from the shapes
    alone: ``BLOCK_TOKENS`` worth, capped by ``max_pages`` and by
    ``BLOCK_BUFFER_BYTES`` for 2 (K, V) x 2 (buffers) blocks of
    ``row_bytes`` (one token of every local KV head) a token."""
    by_vmem = BLOCK_BUFFER_BYTES // (4 * page_size * row_bytes)
    return max(1, min(BLOCK_TOKENS // page_size, by_vmem, max_pages))


def kv_blocks(kv_lens, block_tokens: int) -> int:
    """Blocks of ``block_tokens`` the decode walk takes over sequences that
    hold ``kv_lens`` tokens (0 walks none)."""
    return sum(-(-int(n) // block_tokens) for n in kv_lens)


def paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens,
                           layer=0, *, interpret=False):
    """q [B, Hq, D]; k/v_pages [L, P, page, Hkv, D], a stack of layers'
    pools (or one pool [P, page, Hkv, D]); page_table [B, max_pages];
    seq_lens [B]; layer: which pool of the stack -> [B, Hq, D]."""
    if supported() or interpret:
        from . import kernel
        return kernel.paged_decode_attention_fwd(
            q, k_pages, v_pages, page_table, seq_lens, layer,
            interpret=interpret)
    from . import ref
    return ref.paged_decode_attention(q, k_pages, v_pages, page_table,
                                      seq_lens, layer)


def paged_prefill_attention(q, k_pages, v_pages, page_row, start, total_len,
                            *, interpret=False):
    """Chunked-prefill attention for one sequence (see kernel/ref docstrings).
    q [C, Hq, D]; page_row [max_pages]; total_len = start + valid chunk
    tokens -> [C, Hq, D]."""
    if supported() or interpret:
        from . import kernel
        return kernel.paged_prefill_attention_fwd(
            q, k_pages, v_pages, page_row, start, total_len,
            interpret=interpret)
    from . import ref
    return ref.paged_prefill_attention(q, k_pages, v_pages, page_row, start,
                                       total_len)
