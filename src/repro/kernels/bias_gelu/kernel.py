"""Pallas TPU kernel: fused bias-add + GeLU (tanh approximation, as in BERT).

The paper (§3.2.3) measures GeLU as memory-latency *and* bandwidth bound with
~1 op/byte; fusing the bias-add halves its HBM passes. Elementwise 2-D tiling.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_R = 256
_SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)


def _bias_gelu_kernel(x_ref, b_ref, y_ref):
    x = x_ref[...].astype(jnp.float32)
    if b_ref is not None:
        x = x + b_ref[...].astype(jnp.float32)
    inner = _SQRT_2_OVER_PI * (x + 0.044715 * x * x * x)
    y = 0.5 * x * (1.0 + jnp.tanh(inner))
    y_ref[...] = y.astype(y_ref.dtype)


def bias_gelu(x, bias=None, *, interpret: bool = False):
    """x: [R, F]; bias: [F] or None."""
    r, f = x.shape
    tile = min(TILE_R, r)
    assert r % tile == 0, (r, tile)
    row = pl.BlockSpec((tile, f), lambda i: (i, 0))
    if bias is not None:
        vec = pl.BlockSpec((f,), lambda i: (0,))
        return pl.pallas_call(
            # jaxlint: allow[pallas-grid-floordiv] r % tile asserted above
            _bias_gelu_kernel, grid=(r // tile,),
            in_specs=[row, vec], out_specs=row,
            out_shape=jax.ShapeDtypeStruct((r, f), x.dtype),
            name="bias_gelu", interpret=interpret)(x, bias)
    return pl.pallas_call(
        # jaxlint: allow[pallas-grid-floordiv] r % tile asserted above
        lambda xr, yr: _bias_gelu_kernel(xr, None, yr), grid=(r // tile,),
        in_specs=[row], out_specs=row,
        out_shape=jax.ShapeDtypeStruct((r, f), x.dtype),
        name="gelu", interpret=interpret)(x)
