"""Pallas TPU kernel: fused unembed GEMM + sampling epilogue, logits
VMEM-resident.

One grid pass over vocab tiles: each step multiplies the (revisited) hidden
block [S, D] by its [D, tile] slice of the head weight and writes the f32
logits tile into a VMEM scratch that persists across the sequential grid.
The LAST step runs the whole epilogue row by row — greedy argmax, finite
probe, temperature scaling, the sort-free top-k/top-p bisections, and the
canonical inverse-CDF draw of ``ref.head_epilogue`` — on the on-chip logits,
then emits only the ``int32 [S]`` tokens and the ``[S]`` probe. HBM sees one
read of the head weight and never a logits row.

Layout: the scratch is ``[S, V / RED_TILE, RED_TILE]``, one canonical
reduction tile per sublane row, so the epilogue shares its row helpers with
the fused sampling filter (``fused_sampling.kernel``): tile partials are lane
reductions, folds read them back from VMEM one sublane row at a time, and the
draw's per-tile prefix is ``ref.tile_cumsum`` over lane rolls.

VMEM ceiling: the scratch is ``4 * S * V`` bytes — at the serving shapes
(S = decode slots <= 8, V padded to 128) that is ~8 MB even for a 256k
vocab, inside the ~16 MB VMEM budget. Larger S*V would need the carried-
statistics multi-sweep structure of ``ops.py`` instead.

The per-row draw uniforms arrive as an input (``[S]``, computed outside
from the determinism contract's ``fold_in(key(seed), position)`` key):
threefry does not lower inside Mosaic, and the inverse-CDF draw is defined
so one scalar per row is all the randomness the epilogue needs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..fused_sampling import kernel as skernel
from . import ref

LANES = skernel.LANES
_INT_MAX = 2 ** 31 - 1


def _first_argmax(lg, idx):
    """``jnp.argmax`` of one row [n, LANES]: the first maximal lane, or the
    first NaN lane when the row holds one."""
    nan = lg != lg
    first_nan = jnp.min(jnp.where(nan, idx, _INT_MAX))
    first_max = jnp.min(jnp.where(lg == jnp.max(lg), idx, _INT_MAX))
    return jnp.where(first_nan < _INT_MAX, first_nan, first_max)


def _draw_row(lg_f, r, idx, parts_ref, excl_ref):
    """Canonical inverse-CDF draw (``ref.draw_tokens``) of one row."""
    m = jnp.max(lg_f)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    u = jnp.exp(lg_f - safe_m)
    target = r * skernel.fold_rows(u, parts_ref, excl_ref)
    cs = excl_ref[0:u.shape[0], :] + ref.tile_cumsum(u, roll=pltpu.roll)
    tok = jnp.min(jnp.where(cs > target, idx, _INT_MAX))
    return jnp.where(tok == _INT_MAX, 0, tok)


def _head_kernel(x_ref, w_ref, rs_ref, temps_ref, tk_ref, tp_ref,
                 tok_ref, ok_ref, lg_ref, parts_ref, excl_ref, *, n_tiles,
                 tile, sampled, filtered, softcap):
    t = pl.program_id(0)
    # the reference logits are `(x @ w).astype(f32)`, which XLA computes as
    # one f32-accumulating dot (it folds the model-dtype round trip away)
    lt = jnp.dot(x_ref[...], w_ref[...].astype(x_ref.dtype),
                 preferred_element_type=jnp.float32)
    if softcap:
        lt = softcap * jnp.tanh(lt / softcap)
    s, sub = lt.shape[0], tile // LANES
    for row in range(s):
        for j in range(sub):
            lg_ref[row, pl.ds(t * sub + j, 1), :] = \
                lt[row:row + 1, j * LANES:(j + 1) * LANES]

    @pl.when(t == n_tiles - 1)
    def _epilogue():
        n = lg_ref.shape[1]
        vocab = n * LANES
        skernel.zero_pad_rows(parts_ref, excl_ref, n=n)
        idx = (lax.broadcasted_iota(jnp.int32, (n, LANES), 0) * LANES
               + lax.broadcasted_iota(jnp.int32, (n, LANES), 1))

        def row_body(i, carry):
            lg = lg_ref[i]
            ok = jnp.all(jnp.isfinite(lg)).astype(jnp.int32)
            tok = _first_argmax(lg, idx)
            if sampled:
                temp = temps_ref[i]
                lg_s = lg / jnp.where(temp > 0, temp, 1.0)
                if filtered:
                    lg_s = skernel.filter_row(lg_s, tk_ref[i], tp_ref[i],
                                              parts_ref, vocab=vocab)
                drawn = _draw_row(lg_s, rs_ref[i], idx, parts_ref, excl_ref)
                tok = jnp.where(temp > 0, drawn, tok)
            tok_ref[pl.ds(i, 1), :] = jnp.broadcast_to(tok, (1, 1))
            ok_ref[pl.ds(i, 1), :] = jnp.broadcast_to(ok, (1, 1))
            return carry

        lax.fori_loop(0, lg_ref.shape[0], row_body, 0)


def head_tokens(x: jax.Array, w: jax.Array, rs: jax.Array, temps: jax.Array,
                top_k: jax.Array, top_p: jax.Array, *, sampled: bool,
                filtered: bool, softcap=None, interpret: bool = False):
    """``x`` [S, D] (model dtype), ``w`` [D, V] head weight, V a RED_TILE
    multiple -> ``(tokens int32 [S], ok bool [S])``, bit-identical to
    ``ref.head_epilogue`` on the materialized logits."""
    s, d = x.shape
    v = w.shape[1]
    assert v % LANES == 0, (v, LANES)
    tile = ref.gemm_tile(v)
    n_tiles = v // tile
    n = v // LANES
    scalar = pl.BlockSpec(memory_space=pltpu.SMEM)
    col = pl.BlockSpec((s, 1), lambda t: (0, 0))
    n_fold = skernel.fold_scratch_rows(n)
    tok, ok = pl.pallas_call(
        functools.partial(_head_kernel, n_tiles=n_tiles, tile=tile,
                          sampled=sampled, filtered=filtered,
                          softcap=softcap),
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((s, d), lambda t: (0, 0)),
            pl.BlockSpec((d, tile), lambda t: (0, t)),
            scalar, scalar, scalar, scalar,
        ],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((s, 1), jnp.int32),
                   jax.ShapeDtypeStruct((s, 1), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((s, n, LANES), jnp.float32),
                        pltpu.VMEM((n_fold, LANES), jnp.float32),
                        pltpu.VMEM((n_fold, LANES), jnp.float32)],
        interpret=interpret,
    )(x, w, rs.astype(jnp.float32), temps.astype(jnp.float32),
      top_k.astype(jnp.int32), top_p.astype(jnp.float32))
    return tok[:, 0], ok[:, 0].astype(bool)
