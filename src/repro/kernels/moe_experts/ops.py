"""Dispatch wrapper for the grouped expert MLP of a dropless MoE layer.

``moe_experts`` computes, for each token, the weighted sum of the SwiGLU
outputs of the held experts it picked. On a TPU (and under
``interpret=True``) only the assignments to held experts are computed: they
are sorted by expert, each expert's rows padded to whole tiles of ``TM``,
and ``kernel.grouped_swiglu`` runs each expert's MLP over its own rows
alone; the outputs are then weighted and summed back onto their tokens.
Elsewhere the plain per-expert ``ref.py`` runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kernel, ref

# rows of one tile: a bf16 sublane tile
TM = 16


def supported() -> bool:
    return jax.default_backend() == "tpu"


def max_tiles(assignments: int, experts: int, tm: int = TM) -> int:
    """Tiles enough for any routing of ``assignments`` picks over
    ``experts`` held experts: each expert with rows pads to a whole tile."""
    return -(-assignments // tm) + min(experts, assignments)


def group_sizes(expert_ids, experts: int):
    """Assignments [T, k] per held expert [E] (ids outside [0, E) are not
    held)."""
    return jnp.sum(expert_ids.reshape(-1, 1)
                   == jnp.arange(experts, dtype=expert_ids.dtype),
                   axis=0, dtype=jnp.int32)


def moe_experts(x, expert_ids, weights, w1, w3, w2, *,
                interpret: bool = False, tm: int = TM):
    """x [T, D], expert_ids [T, k] int32 (local ids; outside [0, E) = not
    held), weights [T, k] f32, w1 / w3 [E, D, F], w2 [E, F, D] -> [T, D]
    in x's dtype: per token, the sum over its held picks of the pick's
    weight times that expert's SwiGLU output."""
    if not (interpret or supported()):
        return ref.moe_experts(x, expert_ids, weights, w1, w3, w2)
    return grouped(x, expert_ids, weights, w1, w3, w2, interpret=interpret,
                   tm=tm)


def grouped(x, expert_ids, weights, w1, w3, w2, *, interpret: bool = False,
            tm: int = TM):
    """``moe_experts`` through the kernel on any backend that compiles it."""
    t, d = x.shape
    n = expert_ids.size
    e = w1.shape[0]
    ids = expert_ids.reshape(n)
    held = (ids >= 0) & (ids < e)
    key = jnp.where(held, ids, e)
    order = jnp.argsort(key, stable=True)
    sorted_e = key[order]
    token = order // expert_ids.shape[1]
    sizes = group_sizes(ids, e)                             # [E]
    tiles = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    first_tile = tile_end - tiles
    first_row = jnp.cumsum(sizes) - sizes
    n_tiles = max_tiles(n, e, tm)
    # each held assignment's row in the tiled layout; the rest go to a
    # trailing row that is cut off
    e_c = jnp.minimum(sorted_e, e - 1)
    dest = jnp.where(sorted_e < e,
                     first_tile[e_c] * tm + jnp.arange(n) - first_row[e_c],
                     n_tiles * tm)
    xs = jnp.zeros((n_tiles * tm + 1, d), x.dtype).at[dest].set(
        x[token])[:-1]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles), side="right"),
        e - 1).astype(jnp.int32)
    out = kernel.grouped_swiglu(tile_expert, tile_end[-1:], xs,
                                w1.astype(x.dtype), w3.astype(x.dtype),
                                w2.astype(x.dtype), tm=tm,
                                interpret=interpret)
    w = jnp.where(sorted_e < e, weights.reshape(n)[order], 0.0)
    contrib = out[jnp.minimum(dest, n_tiles * tm - 1)] * w[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[token].add(contrib)
    return y.astype(x.dtype)
