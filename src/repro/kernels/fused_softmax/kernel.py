"""Pallas TPU kernel: fused scale + causal-mask + softmax over score rows.

The paper's "Scale, Mask, Soft." ops are separate memory-bound kernels on the
profiled GPU (Fig 8); fused here into one VMEM-resident pass per row tile:
one read + one write of the [Sq, Sk] scores instead of ~6.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30
TILE_Q = 128


def _softmax_kernel(s_ref, y_ref, *, scale, causal, q_offset, tile_q):
    i = pl.program_id(1)
    x = s_ref[...].astype(jnp.float32) * scale
    if causal:
        sq, sk = x.shape[-2], x.shape[-1]
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) \
            + i * tile_q + q_offset
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        x = jnp.where(cols <= rows, x, NEG_INF)
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    y_ref[...] = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(y_ref.dtype)


def scale_mask_softmax(s, *, scale: float, causal: bool, q_offset: int = 0,
                       interpret: bool = False):
    """s: [N, Sq, Sk] (N = batch*heads)."""
    n, sq, sk = s.shape
    tile = min(TILE_Q, sq)
    assert sq % tile == 0
    spec = pl.BlockSpec((1, tile, sk), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        functools.partial(_softmax_kernel, scale=scale, causal=causal,
                          q_offset=q_offset, tile_q=tile),
        # jaxlint: allow[pallas-grid-floordiv] sq % tile asserted above
        grid=(n, sq // tile),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n, sq, sk), s.dtype),
        name="scale_mask_softmax",
        interpret=interpret,
    )(s)
