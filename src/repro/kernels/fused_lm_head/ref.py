"""Full-logits oracle for the fused LM-head epilogue — and the ONE place the
token *draw* is defined.

The serving sampler historically drew with ``jax.random.categorical``, whose
Gumbel-noise formulation needs one noise value per vocab entry — a ``[S, V]``
tensor a streaming epilogue cannot afford and a Pallas kernel cannot generate
(threefry does not lower inside Mosaic). This module replaces it with the
classic **inverse-CDF draw**: one uniform per row, drawn OUTSIDE the kernel
from the determinism contract's ``fold_in(key(seed), position)`` key, then a
prefix-sum walk over the (filtered, temperature-scaled) softmax masses. The
draw is statistically exact categorical sampling and is defined entirely in
terms of the canonical tiled-sequential reductions of
``kernels.fused_sampling.ref`` — so a vocab-streaming implementation that
only ever holds one ``[S, tile]`` block reproduces it bit-for-bit.

Canonical draw (shared by every implementation)
-----------------------------------------------
Given final filtered scaled logits ``lg_f`` [S, V] and per-row uniforms
``rs`` in [0, 1):

1. ``m = max(lg_f)`` per row; ``safe_m = m`` where finite else 0.
2. ``u = exp(lg_f - safe_m)`` (0 at masked entries).
3. ``Z = fold_partials(tile_partial_sums(u))`` — the canonical
   tiled-sequential row sum.
4. ``target = rs * Z``.
5. The token is the FIRST index ``j`` (global index order) whose inclusive
   prefix mass exceeds ``target``, where the prefix at lane ``l`` of tile
   ``t`` is ``acc_t + tile_cumsum(u_tile)[l]`` — ``acc_t`` the sequential
   fold of the *partials* of tiles ``0..t-1`` (the same adds as step 3) and
   :func:`tile_cumsum` the within-tile prefix in its fixed log-step
   association (``jnp.cumsum`` leaves the association to the backend, and
   has no lowering inside a TPU kernel).
6. If no lane ever exceeds ``target`` the token is 0. That covers both the
   degenerate all-``-inf`` row (``Z == 0``, ``u == 0`` everywhere) and the
   measure-zero rounding edge where ``rs * Z`` lands at or above the final
   prefix — deterministically, on every implementation.

``head_epilogue`` then composes the whole fused-decode epilogue —
greedy argmax on the raw logits, the finite-ness probe, temperature scaling,
the ``fused_sampling`` top-k/top-p filter, this draw — as the oracle the
streaming ``ops.py`` path and the Pallas kernel are tested against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..fused_sampling import ref as sref

RED_TILE = sref.RED_TILE


def gemm_tile(v: int) -> int:
    """The vocab-block width the streaming implementations sweep with: the
    widest of (512, 384, 256, 128) dividing ``v`` — every candidate is a
    RED_TILE multiple, so the canonical reduction tiles nest inside GEMM
    tiles exactly. A ``v`` none divides (possible only for unit-test vocabs;
    the engine always serves ``pad_vocab`` multiples of 128) degrades to one
    full-width block, with the reductions zero-padding internally."""
    for t in (512, 384, 256, 128):
        if v % t == 0:
            return t
    return v


def row_uniforms(seeds: jax.Array, positions: jax.Array) -> jax.Array:
    """The per-row draw uniforms [S] float32 in [0, 1): one
    ``jax.random.uniform`` from the determinism contract's
    ``fold_in(key(seed), position)`` key. Defined here once so the unfused
    sampler, the streaming epilogue, and the engine's fused decode step all
    derive the identical ``rs`` for the same (seed, position)."""
    def one(s, p):
        key = jax.random.fold_in(jax.random.key(s), p)
        return jax.random.uniform(key, (), jnp.float32)
    return jax.vmap(one)(seeds.astype(jnp.uint32),
                         positions.astype(jnp.int32))


def pad_tiles(u: jax.Array) -> jax.Array:
    """``u`` [S, V] -> [S, n, RED_TILE] with zero right-padding — the tile
    view both the fold partials and the draw's per-tile cumsum walk use.
    Zero pads are exact for the mass terms and can never be drawn (their
    inclusive prefix equals the preceding real lane's)."""
    s, v = u.shape
    pad = (-v) % RED_TILE
    if pad:
        u = jnp.concatenate([u, jnp.zeros((s, pad), u.dtype)], axis=-1)
    return u.reshape(s, (v + pad) // RED_TILE, RED_TILE)


def tile_cumsum(u: jax.Array, roll=jnp.roll) -> jax.Array:
    """Inclusive prefix sum along the last axis in the canonical log-step
    (Hillis-Steele) association: step ``s = 1, 2, 4, ...`` adds to every
    lane the running value ``s`` lanes to its left (zero where there is
    none). ``roll`` is ``jnp.roll`` or, inside a kernel, ``pltpu.roll``;
    both move data exactly, so every implementation performs the same
    float adds."""
    lane = lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
    s = 1
    while s < u.shape[-1]:
        u = u + jnp.where(lane >= s, roll(u, s, u.ndim - 1), 0.0)
        s *= 2
    return u


def draw_tokens(lg_f: jax.Array, rs: jax.Array) -> jax.Array:
    """Canonical inverse-CDF draw: filtered scaled logits ``lg_f`` [S, V] +
    uniforms ``rs`` [S] -> int32 tokens [S]. See the module docstring for
    the exact (bit-reproducible) definition."""
    s, v = lg_f.shape
    m = jnp.max(lg_f, axis=-1)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    u = pad_tiles(jnp.exp(lg_f.astype(jnp.float32) - safe_m[:, None]))
    parts = jnp.sum(u, axis=-1)                          # [S, n] tile masses
    target = rs.astype(jnp.float32) * sref.fold_partials(parts)

    def body(i, carry):
        acc, tok = carry
        tile = lax.dynamic_index_in_dim(u, i, axis=1, keepdims=False)
        cs = acc[:, None] + tile_cumsum(tile)            # [S, RED_TILE]
        hit = cs > target[:, None]
        idx = (jnp.argmax(hit, axis=-1).astype(jnp.int32)
               + i.astype(jnp.int32) * RED_TILE)
        tok = jnp.where((tok < 0) & jnp.any(hit, axis=-1), idx, tok)
        part = lax.dynamic_index_in_dim(parts, i, axis=1, keepdims=False)
        return acc + part, tok

    acc0 = jnp.zeros((s,), jnp.float32)
    tok0 = jnp.full((s,), -1, jnp.int32)
    _, tok = lax.fori_loop(0, u.shape[1], body, (acc0, tok0))
    return jnp.where(tok < 0, 0, tok)


def head_epilogue(logits, rs, temps, top_k, top_p, *, sampled: bool,
                  filtered: bool, filter_fn=None):
    """Whole fused-decode epilogue on MATERIALIZED logits [S, V] — the
    oracle. Returns ``(tokens int32 [S], ok bool [S])`` where ``ok`` is the
    per-row all-finite probe the engine's sanitizer consumes.

    ``sampled``/``filtered`` are static flags matching the engine's jit
    variants; ``filter_fn`` defaults to the sort-based
    ``fused_sampling.ref.filter_logits_ref`` oracle (any of the package's
    bit-identical filter implementations is equivalent)."""
    ok = jnp.all(jnp.isfinite(logits), axis=-1)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if not sampled:
        return greedy, ok
    temps = temps.astype(jnp.float32)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    lg = logits.astype(jnp.float32) / safe_t[:, None]
    if filtered:
        fn = filter_fn if filter_fn is not None else sref.filter_logits_ref
        lg = fn(lg, top_k.astype(jnp.int32), top_p.astype(jnp.float32))
    drawn = draw_tokens(lg, rs)
    return jnp.where(temps > 0, drawn, greedy), ok
