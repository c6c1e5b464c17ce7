"""Pallas TPU kernel: the SwiGLU expert MLP over rows grouped by expert.

Rows arrive sorted by expert, each expert's group padded to whole tiles of
``tm`` rows (``ops.moe_experts`` lays them out). The grid walks the tiles
(and, where an expert's weights do not fit VMEM twice over, blocks of its
intermediate width): tile ``t`` multiplies its rows by the weights of
``tile_expert[t]`` — ``w1`` and ``w3``, the silu gate, then ``w2`` into an
f32 accumulator. Only the first ``num_tiles`` tiles hold rows. Consecutive
tiles of one expert reuse its weight blocks, and the tiles past
``num_tiles`` map every input to the last block already fetched, so the
pipeline starts a weight DMA only for experts that have rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM for the weight blocks, two of each of w1, w3 and w2 (the pipeline's
# double buffer): a whole deepseek-moe expert (2048 x 1408 in bf16) fits
VMEM_WEIGHT_BYTES = 40 << 20


def block_ff(d: int, f: int, itemsize: int) -> int:
    """The intermediate width one grid step takes: all of ``f`` where two
    copies of the three blocks fit ``VMEM_WEIGHT_BYTES``, else the largest
    multiple of 128 dividing ``f`` that does."""
    def fits(tf):
        return 6 * d * tf * itemsize <= VMEM_WEIGHT_BYTES
    if fits(f) or f % 128:
        return f
    return max(tf for tf in range(128, f + 1, 128)
               if f % tf == 0 and (fits(tf) or tf == 128))


def _moe_experts_kernel(tile_expert_ref, num_tiles_ref, x_ref, w1_ref,
                        w3_ref, w2_ref, o_ref, acc_ref):
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(t < num_tiles_ref[0])
    def _():
        x = x_ref[...]
        a = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
        b = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
        # silu spelled out: bf16 logistic does not lower
        h = a / (1.0 + jnp.exp(-a)) * b
        acc_ref[...] += jnp.dot(h.astype(w2_ref.dtype), w2_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(f == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_swiglu(tile_expert, num_tiles, x, w1, w3, w2, *, tm: int,
                   interpret: bool = False):
    """tile_expert [n_tiles] int32, num_tiles [1] int32, x [n_tiles * tm, D]
    (rows grouped by expert, whole tiles), w1 / w3 [E, D, F], w2 [E, F, D]
    -> [n_tiles * tm, D] float32; the rows of tiles past ``num_tiles`` are
    zero."""
    m, d = x.shape
    e, _, f = w1.shape
    n_tiles = m // tm
    assert n_tiles * tm == m, (m, tm)
    tf = block_ff(d, f, w1.dtype.itemsize)
    nf = f // tf

    def live(t, n):
        # the last tile that holds rows, for the tiles past it
        return lax.min(t, lax.max(n[0] - 1, 0))

    def rows(t, fi, te, n):
        return live(t, n), 0

    def ff_block(t, fi, n):
        # past the rows: stay on the last block fetched
        return lax.select(t < n[0], fi, nf - 1)

    def up(t, fi, te, n):
        return te[live(t, n)], 0, ff_block(t, fi, n)

    def down(t, fi, te, n):
        return te[live(t, n)], ff_block(t, fi, n), 0

    return pl.pallas_call(
        _moe_experts_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles, nf),
            in_specs=[pl.BlockSpec((tm, d), rows),
                      pl.BlockSpec((None, d, tf), up),
                      pl.BlockSpec((None, d, tf), up),
                      pl.BlockSpec((None, tf, d), down)],
            out_specs=pl.BlockSpec((tm, d), lambda t, fi, te, n: (t, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=6 * d * tf * w1.dtype.itemsize + (16 << 20)),
        name="moe_experts",
        interpret=interpret,
    )(tile_expert.astype(jnp.int32), num_tiles.astype(jnp.int32), x, w1,
      w3, w2)
