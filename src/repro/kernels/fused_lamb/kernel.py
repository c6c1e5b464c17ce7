"""Pallas TPU kernels for fused LAMB (paper Fig 3 / Fig 13's "fused optimizer").

Two kernels, matching the paper's LAMB Stage 1 / Stage 2 split:

  stage1: one HBM pass reading (w, g, m, v) and writing (m', v', u) + per-tile
          partial sums of ||w||^2 and ||u||^2 — everything the trust ratio needs.
  stage2: one HBM pass applying w' = w - lr * r * u.

Total traffic: 4 reads + 4 writes of model-size arrays vs ~11 passes unfused —
this is exactly the Takeaway-8 "LAMB reads 4x the model size" bottleneck the
paper says accelerators must optimize.

Layout: flat [rows, F] fp32 (the ZeRO state layout); grid tiles F with the rows
axis as the leading grid dim so per-row partial norms land in [rows, tiles].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_F = 2048  # lane-aligned (128) and small enough for 6 operands in VMEM


def _stage1_kernel(w_ref, g_ref, m_ref, v_ref, scal_ref,
                   m_out, v_out, u_out, wsq_out, usq_out,
                   *, beta1, beta2, eps, weight_decay):
    ginv = scal_ref[0]
    c1 = scal_ref[1]
    c2 = scal_ref[2]
    w = w_ref[...]
    gn = g_ref[...] * ginv
    m_new = beta1 * m_ref[...] + (1.0 - beta1) * gn
    v_new = beta2 * v_ref[...] + (1.0 - beta2) * gn * gn
    u = (m_new * c1) / (jnp.sqrt(v_new * c2) + eps) + weight_decay * w
    m_out[...] = m_new
    v_out[...] = v_new
    u_out[...] = u
    wsq_out[0, 0] = jnp.sum(w * w)
    usq_out[0, 0] = jnp.sum(u * u)


def _stage2_kernel(w_ref, u_ref, r_ref, w_out, *, lr):
    w_out[...] = w_ref[...] - lr * r_ref[0] * u_ref[...]


def lamb_stage1(w, g, m, v, scalars, *, beta1, beta2, eps, weight_decay,
                interpret: bool = False):
    """w/g/m/v: [R, F] fp32 (F % TILE_F == 0); scalars: [3] (ginv, c1, c2)."""
    r, f = w.shape
    assert f % TILE_F == 0, (f, TILE_F)
    tiles = f // TILE_F
    grid = (r, tiles)
    row_tile = pl.BlockSpec((1, TILE_F), lambda i, j: (i, j))
    scal = pl.BlockSpec((3,), lambda i, j: (0,))
    part = pl.BlockSpec((1, 1), lambda i, j: (i, j))
    kernel = functools.partial(_stage1_kernel, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay)
    m_new, v_new, u, wsq, usq = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_tile, row_tile, row_tile, row_tile, scal],
        out_specs=[row_tile, row_tile, row_tile, part, part],
        out_shape=[
            jax.ShapeDtypeStruct((r, f), jnp.float32),
            jax.ShapeDtypeStruct((r, f), jnp.float32),
            jax.ShapeDtypeStruct((r, f), jnp.float32),
            jax.ShapeDtypeStruct((r, tiles), jnp.float32),
            jax.ShapeDtypeStruct((r, tiles), jnp.float32),
        ],
        name="lamb_moments",
        interpret=interpret,
    )(w, g, m, v, scalars)
    return m_new, v_new, u, wsq, usq


def lamb_stage2(w, u, rr, *, lr, interpret: bool = False):
    """w/u: [R, F]; rr: [R, 1] per-row trust ratios."""
    r, f = w.shape
    tiles = f // TILE_F
    row_tile = pl.BlockSpec((1, TILE_F), lambda i, j: (i, j))
    rspec = pl.BlockSpec((1, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_stage2_kernel, lr=lr),
        grid=(r, tiles),
        in_specs=[row_tile, row_tile, rspec],
        out_specs=row_tile,
        out_shape=jax.ShapeDtypeStruct((r, f), jnp.float32),
        name="lamb_update",
        interpret=interpret,
    )(w, u, rr)
