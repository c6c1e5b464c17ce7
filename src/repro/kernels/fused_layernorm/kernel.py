"""Pallas TPU kernel: fused residual-add + LayerNorm/RMSNorm.

Unfused, this chain is 4 HBM passes (add out, mean/var reduce, normalize read,
write); fused it is one read of (x, residual) and one write of y, with the
row statistics living in VMEM — the 6-8x traffic reduction the paper measures
in Fig 13. Rows are tiled [TILE_R, D]; D must fit VMEM (all assigned archs:
d_model <= 12288 -> <= 96 KiB fp32 per row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_R = 256


def _ln_kernel(x_ref, res_ref, scale_ref, bias_ref, y_ref, *, eps, rms):
    h = x_ref[...].astype(jnp.float32) + res_ref[...].astype(jnp.float32)
    if rms:
        var = jnp.mean(h * h, axis=-1, keepdims=True)
        y = h * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(h, axis=-1, keepdims=True)
        c = h - mu
        var = jnp.mean(c * c, axis=-1, keepdims=True)
        y = c * jax.lax.rsqrt(var + eps)
    y = y * scale_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        y = y + bias_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)


def fused_residual_layernorm(x, residual, scale, bias=None, *, eps=1e-5,
                             rms: bool = False, interpret: bool = False):
    """x, residual: [R, D]; scale/bias: [D]."""
    r, d = x.shape
    tile = min(TILE_R, r)
    assert r % tile == 0, (r, tile)
    row = pl.BlockSpec((tile, d), lambda i: (i, 0))
    vec = pl.BlockSpec((d,), lambda i: (0,))
    args = [x, residual, scale]
    in_specs = [row, row, vec]
    if bias is not None:
        args.append(bias)
        in_specs.append(vec)
        kern = functools.partial(_ln_kernel, eps=eps, rms=rms)
    else:
        kern = functools.partial(
            lambda xr, rr, sr, yr, *, eps, rms:
            _ln_kernel(xr, rr, sr, None, yr, eps=eps, rms=rms),
            eps=eps, rms=rms)
    return pl.pallas_call(
        kern,
        # jaxlint: allow[pallas-grid-floordiv] r % tile asserted above
        grid=(r // tile,),
        in_specs=in_specs,
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((r, d), x.dtype),
        interpret=interpret,
    )(*args)


def _resnorm_kernel(y_ref, x_ref, scale_ref, bias_ref, h_ref, xo_ref, *,
                    eps, kind):
    # model-dtype add (bit-faithful to the unfused `x = x + y`), fp32 stats
    x2 = x_ref[...] + y_ref[...]
    xf = x2.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        h = xf * jax.lax.rsqrt(var + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        h = (xf - mu) * jax.lax.rsqrt(var + eps)
    h = h * scale_ref[...].astype(jnp.float32)
    if bias_ref is not None:
        h = h + bias_ref[...].astype(jnp.float32)
    h_ref[...] = h.astype(h_ref.dtype)
    xo_ref[...] = x2


def decode_residual_norm(y, x, scale, bias=None, *, eps=1e-5,
                         kind: str = "rmsnorm", interpret: bool = False):
    """Decode-shaped fused residual+norm: y, x [R, D] -> (normed [R, D],
    x+y [R, D]). One read of (y, x), one write of each output — the decode
    layer's three residual-stream HBM round-trips become one."""
    r, d = x.shape
    tile = min(TILE_R, r)
    assert r % tile == 0, (r, tile)
    row = pl.BlockSpec((tile, d), lambda i: (i, 0))
    vec = pl.BlockSpec((d,), lambda i: (0,))
    args = [y, x, scale]
    in_specs = [row, row, vec]
    if bias is not None:
        args.append(bias)
        in_specs.append(vec)
        kern = functools.partial(_resnorm_kernel, eps=eps, kind=kind)
    else:
        kern = functools.partial(
            lambda yr, xr, sr, hr, xo, *, eps, kind:
            _resnorm_kernel(yr, xr, sr, None, hr, xo, eps=eps, kind=kind),
            eps=eps, kind=kind)
    return pl.pallas_call(
        kern,
        # jaxlint: allow[pallas-grid-floordiv] r % tile asserted above
        grid=(r // tile,),
        in_specs=in_specs,
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((r, d), x.dtype),
                   jax.ShapeDtypeStruct((r, d), x.dtype)],
        interpret=interpret,
    )(*args)


def _gated_kernel(y_ref, z_ref, scale_ref, o_ref, *, eps):
    y = y_ref[...]
    z = z_ref[...]
    # jax.nn.sigmoid spelled out with a constant of the input dtype: Mosaic
    # lowers a bf16 logistic with an f32 one (a verifier error). XLA expands
    # its logistic to these same ops, each rounded to the input dtype.
    one = jnp.ones((), z.dtype)
    yf = (y * (z * (one / (one + jnp.exp(-z))))).astype(jnp.float32)
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    o_ref[...] = (yf * jax.lax.rsqrt(var + eps)
                  * scale_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def gated_rmsnorm(y, z, scale, *, eps=1e-5, interpret: bool = False):
    """SiLU-gated RMSNorm (mamba mixer epilogue): y, z [R, C] -> [R, C],
    gate + stats + normalize in one VMEM pass."""
    r, d = y.shape
    tile = min(TILE_R, r)
    assert r % tile == 0, (r, tile)
    row = pl.BlockSpec((tile, d), lambda i: (i, 0))
    vec = pl.BlockSpec((d,), lambda i: (0,))
    return pl.pallas_call(
        functools.partial(_gated_kernel, eps=eps),
        # jaxlint: allow[pallas-grid-floordiv] r % tile asserted above
        grid=(r // tile,),
        in_specs=[row, row, vec],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((r, d), y.dtype),
        interpret=interpret,
    )(y, z, scale)
