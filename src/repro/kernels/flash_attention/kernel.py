"""Pallas TPU flash-attention forward (GQA, causal/window/cache-length masking).

TPU adaptation of the paper's "memory-bound attention B-GEMMs + scale/mask/softmax"
finding (Takeaways 7/9): instead of materializing [Sq, Sk] scores in HBM and
running three separate memory-bound EW kernels over them, each (batch, q-head,
q-block) grid cell streams KV blocks through VMEM, keeping a [block_q, block_kv]
score tile and fp32 (o, m, l) accumulators resident. HBM traffic drops from
O(Sq*Sk) to O(Sq*D + Sk*D) per head.

MXU alignment: block_q/block_kv are multiples of 128; D = head_dim (64/128 for
all assigned archs) is the contraction dim of both tile GEMMs.

Layout: q [B, Hq, Sq, D]; k/v [B, Hkv, Sk, D]. Grid (B*Hq, Sq/block_q); the kv
loop is a fori_loop inside the kernel so the q-tile accumulators never leave
VMEM. Backward runs through the pure-JAX custom-VJP chunked path (same math).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, kvlen_ref, o_ref, *,
                  block_q, block_kv, sk, causal, q_offset, window, scale):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [block_q, D]
    kv_len = kvlen_ref[0]

    nblocks = sk // block_kv

    def body(j, carry):
        o, m, l = carry
        k = k_ref[0, pl.dslice(j * block_kv, block_kv)].astype(jnp.float32)
        v = v_ref[0, pl.dslice(j * block_kv, block_kv)].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bkv]
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            + qi * block_q + q_offset
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_kv
        mask = cols < kv_len
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[:, None] + jax.lax.dot(p, v)
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, nblocks, body, (o0, m0, l0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, kv_len, *, causal, q_offset=0, window=0,
                        block_q=128, block_kv=512, interpret=False):
    """q [B,Hq,Sq,D]; k/v [B,Hkv,Sk,D]; kv_len [B] -> o [B,Hq,Sq,D]."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    block_q = min(block_q, sq)
    block_kv = min(block_kv, sk)
    assert sq % block_q == 0 and sk % block_kv == 0
    scale = 1.0 / (d ** 0.5)

    q4 = q.reshape(b * hq, sq, d)
    # repeat kv per q-head group (views only — blocks are fetched per grid cell)
    k4 = jnp.repeat(k, g, axis=1).reshape(b * hq, sk, d)
    v4 = jnp.repeat(v, g, axis=1).reshape(b * hq, sk, d)
    kvl = jnp.repeat(kv_len, hq).astype(jnp.int32)

    kern = functools.partial(
        _flash_kernel, block_q=block_q, block_kv=block_kv, sk=sk,
        causal=causal, q_offset=q_offset, window=window, scale=scale)
    out = pl.pallas_call(
        kern,
        # jaxlint: allow[pallas-grid-floordiv] sq % block_q asserted above
        grid=(b * hq, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda n, i: (n, i, 0)),
            pl.BlockSpec((1, sk, d), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda n, i: (n, 0, 0)),
            pl.BlockSpec((1,), lambda n, i: (n,)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        name="flash_attention",
        interpret=interpret,
    )(q4, k4, v4, kvl)
    return out.reshape(b, hq, sq, d)
