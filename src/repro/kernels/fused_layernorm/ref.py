"""Oracles for the fused residual+norm kernels (paper Fig 13 'LN' fusion).

Two flavors with different numerics contracts:

* :func:`fused_residual_layernorm` — the training/prefill fusion: the
  residual add runs in fp32 (numerics-*improving* vs the unfused bf16 add),
  so its parity tests are tolerance-based.
* :func:`decode_residual_norm` / :func:`gated_rmsnorm` — the decode-path
  fusions: the add stays in the MODEL dtype and the norm duplicates
  ``models.layers._apply_norm`` / ``models.ssm._gated_rmsnorm`` operation
  for operation (duplicated here rather than imported to keep the kernels
  layer import-cycle-free), so the fused decode stack is BIT-identical to
  the unfused one — the property that lets the serving engine's paged
  stack emit the static engine's tokens. Input shapes are preserved (no
  flattening) so the fp32 row reductions see exactly the shapes the
  unfused path reduces.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def fused_residual_layernorm(x, residual, scale, bias=None, *, eps=1e-5,
                             rms: bool = False):
    """y = norm(x + residual) * scale (+ bias); stats in fp32."""
    h = (x.astype(jnp.float32) + residual.astype(jnp.float32))
    if rms:
        var = jnp.mean(jnp.square(h), axis=-1, keepdims=True)
        y = h * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(h, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(h - mu), axis=-1, keepdims=True)
        y = (h - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


def _norm(kind: str, x, scale, bias, eps):
    """Verbatim ``models.layers._apply_norm`` math (see module docstring
    for why it is duplicated instead of imported)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps)
    elif kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def decode_residual_norm(y, x, scale, bias=None, *, kind: str = "rmsnorm",
                         eps=1e-5):
    """Fused ``x += y; h = norm(x)`` of the decode residual stream ->
    ``(h, x_new)``. The add runs in the model dtype and the norm is the
    verbatim ``_apply_norm`` math, so the pair is bit-identical to the
    unfused two-op sequence on every backend."""
    x2 = x + y
    return _norm(kind, x2, scale, bias, eps), x2


def gated_rmsnorm(y, z, scale, eps=1e-5):
    """Verbatim ``models.ssm._gated_rmsnorm``: SiLU-gated RMSNorm of the
    mamba mixer output (the canonical definition — ``models.ssm`` delegates
    here, and the Pallas kernel must match it bit-for-bit)."""
    yf = (y * (z * jax.nn.sigmoid(z))).astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(y.dtype)
