"""Pallas TPU kernel: single-pass fused top-k + nucleus top-p logit filter.

One row of temperature-scaled logits stays VMEM-resident for the whole
epilogue: bit-key conversion, the 32-step top-k count bisection, the masked
softmax mass statistics, and the 32-step nucleus mass bisection all run over
the same block — one HBM read and one HBM write of the logits instead of the
sort-based sampler's multiple sorted copies. The decision predicates are the
canonical ones from ``ref.py``, so the kernel masks bit-identically to both
the jnp streaming path (``ops.py``) and the sort-based oracle.

Layout: a row of V logits is held as ``[V / RED_TILE, RED_TILE]`` — one
canonical reduction tile per sublane row — so the per-tile partial masses
are plain lane reductions, and the sequential fold of those partials
(``ref.fold_partials``) reads them back from a VMEM scratch one sublane row
at a time instead of dynamically slicing a value (which Mosaic cannot
lower). The row helpers below are shared with the fused LM-head kernel.

Mosaic has no unsigned compare, so the bisections run over a signed int32
key that orders floats exactly as ``ref.float_to_key`` does
(``signed = key ^ 0x80000000``); every midpoint is the same bit pattern the
uint32 bisection computes, so both land on the same threshold.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref

BISECT_STEPS = 32
LANES = ref.RED_TILE
SUBLANES = 8
_LOW31 = 0x7FFFFFFF
# ref.TOP_KEY (0xFFFFFFFE) and key 0 in the signed key space
_LO_KEY = -2 ** 31
_HI_KEY = 0x7FFFFFFE


def signed_key(f: jax.Array) -> jax.Array:
    """float32 -> int32 key, ``ref.float_to_key(f) ^ 0x80000000``."""
    b = lax.bitcast_convert_type(f, jnp.int32)
    return jnp.where(b < 0, b ^ _LOW31, b)


def signed_key_to_float(k: jax.Array) -> jax.Array:
    """Inverse of :func:`signed_key`."""
    return lax.bitcast_convert_type(jnp.where(k < 0, k ^ _LOW31, k),
                                    jnp.float32)


def _half(lo, hi, extra):
    """``(hi - lo + extra) >> 1`` with uint32 wrap-around semantics."""
    return lax.shift_right_logical(hi - lo + extra, jnp.int32(1))


def fold_rows(u, parts_ref, excl_ref=None):
    """Canonical mass of one row ``u`` [n, LANES] -> scalar: per-tile
    partial sums (one lane reduction per sublane row) folded strictly left
    to right, exactly ``ref.tiled_row_sum``. ``parts_ref`` is a VMEM
    scratch of ``[n_pad, LANES]`` (``n_pad`` a multiple of 8) whose rows
    past ``n`` hold zeros — adding +0.0 to a nonnegative running mass is
    exact. With ``excl_ref`` given (same shape), row ``t`` receives the fold
    of partials ``0..t-1`` (the draw's entering accumulators)."""
    n = u.shape[0]
    parts_ref[0:n, :] = jnp.broadcast_to(
        jnp.sum(u, axis=-1, keepdims=True), (n, LANES))

    def block(b, acc):
        base = pl.multiple_of(b * SUBLANES, SUBLANES)
        blk = parts_ref[pl.ds(base, SUBLANES), :]
        for r in range(SUBLANES):
            if excl_ref is not None:
                excl_ref[pl.ds(base + r, 1), :] = acc
            acc = acc + blk[r:r + 1, :]
        return acc

    acc = lax.fori_loop(0, parts_ref.shape[0] // SUBLANES, block,
                        jnp.zeros((1, LANES), jnp.float32))
    return acc[0, 0]


def filter_row(lg, top_k, top_p, parts_ref, *, vocab):
    """Top-k then nucleus top-p mask of one row ``lg`` [n, LANES] float32
    (dropped entries at ``-inf``), the canonical semantics of
    ``ref.filter_logits_ref``. ``top_k`` / ``top_p`` are the row's raw
    parameters, ``vocab`` the real row width (lanes past it hold -inf)."""
    keys = signed_key(lg)
    k = jnp.where(top_k <= 0, vocab, jnp.minimum(top_k, vocab))

    def kth_body(_, lohi):
        lo, hi = lohi
        mid = lo + _half(lo, hi, 1)
        cnt = jnp.sum((keys >= mid).astype(jnp.int32))
        ok = cnt >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = lax.fori_loop(0, BISECT_STEPS, kth_body,
                          (jnp.int32(_LO_KEY), jnp.int32(_HI_KEY)))
    # (Mosaic bitcasts vectors only: convert the scalar key as a lane row)
    kth = signed_key_to_float(jnp.broadcast_to(lo, (1, LANES)))
    lg_k = jnp.where(lg < kth, -jnp.inf, lg)

    m = jnp.max(lg_k)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    u = jnp.exp(lg_k - safe_m)
    t = jnp.maximum(top_p * fold_rows(u, parts_ref),
                    jnp.float32(ref.T_FLOOR))
    keys_k = signed_key(lg_k)

    def topp_body(_, lohi):
        lo, hi = lohi
        mid = lo + _half(lo, hi, 0)
        sg = fold_rows(jnp.where(keys_k > mid, u, 0.0), parts_ref)
        ok = sg < t
        return jnp.where(ok, lo, mid + 1), jnp.where(ok, mid, hi)

    _, hi = lax.fori_loop(0, BISECT_STEPS, topp_body,
                          (jnp.int32(_LO_KEY), jnp.int32(_HI_KEY)))
    th = jnp.where(top_p >= 1.0, -jnp.inf,
                   signed_key_to_float(jnp.broadcast_to(hi, (1, LANES))))
    return jnp.where(lg_k < th, -jnp.inf, lg_k)


def zero_pad_rows(*refs, n):
    """Clear the rows past ``n`` of fold scratches (see :func:`fold_rows`)."""
    for r in refs:
        if r.shape[0] > n:
            r[n:, :] = jnp.zeros((r.shape[0] - n, LANES), r.dtype)


def fold_scratch_rows(n: int) -> int:
    return -(-n // SUBLANES) * SUBLANES


def _filter_kernel(tk_ref, tp_ref, lg_ref, y_ref, parts_ref, *, vocab):
    i = pl.program_id(0)
    zero_pad_rows(parts_ref, n=lg_ref.shape[0])
    y_ref[...] = filter_row(lg_ref[...], tk_ref[i], tp_ref[i], parts_ref,
                            vocab=vocab)


def filter_logits(lg: jax.Array, top_k: jax.Array, top_p: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """lg: [S, V] float32; top_k: int32 [S]; top_p: float32 [S]. A V that
    is not a RED_TILE multiple is padded with -inf lanes, which carry no
    mass and never reach the top-k count's threshold."""
    s, v = lg.shape
    pad = (-v) % LANES
    lg = lg.astype(jnp.float32)
    if pad:
        lg = jnp.pad(lg, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    n = (v + pad) // LANES
    row = pl.BlockSpec((None, n, LANES), lambda i: (i, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_filter_kernel, vocab=v),
        grid=(s,),
        in_specs=[smem, smem, row],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((s, n, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((fold_scratch_rows(n), LANES),
                                   jnp.float32)],
        name="fused_sampling_filter",
        interpret=interpret,
    )(top_k.astype(jnp.int32), top_p.astype(jnp.float32),
      lg.reshape(s, n, LANES))
    return out.reshape(s, n * LANES)[:, :v]
