"""Oracle for the Pallas paged decode-attention kernel.

Gathers K/V pages through the page table into the dense [B, L, Hkv, D] layout
and delegates to ``naive_attention`` — the exact numerics of the static
engine's decode path, so engine-parity tests compare like with like.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...models.attention import naive_attention


def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_row: jax.Array,
                            start, total_len) -> jax.Array:
    """Chunked-prefill attention for one sequence against its paged cache.

    q [C, Hq, D] — queries of one prompt chunk, row i at position start + i
    (the chunk's own K/V must already be written into the pages);
    page_row [max_pages]; total_len = start + valid tokens in the chunk.
    -> [C, Hq, D]. Padding rows (position >= total_len) return garbage the
    engine never reads; tokens attend causally to the cached prefix plus the
    chunk itself.
    """
    c, hq, d = q.shape
    _, page_size, hkv, _ = k_pages.shape
    k = k_pages[page_row].reshape(1, -1, hkv, d)
    v = v_pages[page_row].reshape(1, -1, hkv, d)
    kv_len = jnp.asarray(total_len, jnp.int32).reshape(1)
    o = naive_attention(q[None], k, v, causal=True,
                        q_offset=jnp.asarray(start, jnp.int32), kv_len=kv_len)
    return o[0]


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           seq_lens: jax.Array, layer=0) -> jax.Array:
    """Single-query attention against a paged KV cache.

    q [B, Hq, D]; k_pages/v_pages [L, P, page_size, Hkv, D], a stack of
    layers' pools of which ``layer`` is read (or one pool
    [P, page_size, Hkv, D]); page_table [B, max_pages] (physical page ids,
    0 = null page); seq_lens [B] = valid cache length per sequence
    (0 = inactive slot). -> [B, Hq, D]
    """
    b, hq, d = q.shape
    hkv = k_pages.shape[-2]
    at = page_table if k_pages.ndim == 4 else (layer, page_table)
    k = k_pages[at].reshape(b, -1, hkv, d)
    v = v_pages[at].reshape(b, -1, hkv, d)
    o = naive_attention(q[:, None], k, v, causal=False,
                        kv_len=seq_lens.astype(jnp.int32))
    return o[:, 0]
