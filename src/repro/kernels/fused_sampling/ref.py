"""Sort-based oracle for the fused sampling epilogue — and the ONE place the
top-k / top-p filtering semantics are defined.

This module replaces the twin ``jnp.sort`` code paths that used to live
inline in ``serving.sampling`` (one full-vocab sort for the top-k threshold,
a second for the nucleus cumsum). It is the parity oracle the fused kernel
is tested against bit-for-bit (``serving.sampling.sample_tokens`` always
runs the fused filter).

Canonical filtering semantics (shared with ``ops.py`` / ``kernel.py``)
----------------------------------------------------------------------
Given temperature-scaled logits ``lg`` [S, V] and per-row ``top_k`` /
``top_p``:

1. **top-k** — ``kth`` = the k-th largest *value* of the row; every logit
   ``< kth`` is masked to ``-inf``. Ties at the k-th value are all kept
   (a value threshold, not a rank cut), so the mask is independent of sort
   order among equal logits.
2. **top-p** — on the top-k-masked row, with unnormalized softmax masses
   ``U = exp(lg_k - max)`` and ``Z = sum(U)``, the nucleus threshold is the
   smallest kept value ``v`` whose *strictly-greater mass*
   ``SG(v) = sum(U[lg_k > v])`` stays under ``T = top_p * Z``. Every logit
   ``< v`` is masked to ``-inf``. This keeps exactly the maximal descending-
   probability prefix whose exclusive cumulative mass is below ``top_p``
   (the standard nucleus), again with all ties at the boundary kept.

Why thresholds instead of the usual sort + cumsum + rank cut: the decision
predicate ``SG(v) < T`` is a pure function of a candidate *value*, computed
by one masked reduction — so a sort-free implementation (bisection over the
float bit space, ``ops.py``) and this sort-based one (bisection over ranks
of one descending sort) evaluate the *identical* float expressions and must
agree on every threshold bit-for-bit. With the old cumsum formulation the
two implementations would round the running mass differently and could
disagree by one token exactly at nucleus boundaries.

Both bisections converge because ``SG`` is monotone in ``v`` even in
float32: replacing a 0 with a nonnegative term at a fixed position of a
fixed-shape reduction cannot decrease a round-to-nearest sum.

Degenerate rows are defined (and shared) here too: ``top_k <= 0`` or
``top_k >= V`` disables the rank cut; ``top_p >= 1`` keeps everything
explicitly; an out-of-contract ``top_p <= 0`` clamps to "top-1" via the
``T`` floor; an all-``-inf`` row (``Z == 0``) passes through unmasked.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# floor for the nucleus mass target: keeps the bisection's "the top logit is
# always kept" invariant (SG(max) == 0 < T) even when top_p * Z underflows
# to 0 or an out-of-contract top_p <= 0 slips past SamplingParams
T_FLOOR = 1.1754943508222875e-38        # smallest normal float32

# canonical reduction tile: every float mass in this package (and in
# ``kernels.fused_lm_head``, which re-evaluates these predicates while
# streaming the unembed GEMM over vocab blocks) is summed as partial sums
# over consecutive RED_TILE-lane tiles, folded left-to-right in tile order.
# Fixing the association this way is what lets a streaming implementation
# that never holds the full row reproduce the oracle's floats bit-for-bit:
# any vocab-block width that is a multiple of RED_TILE yields the same
# per-tile partials, and the sequential fold is the same add sequence.
RED_TILE = 128


# ------------------------------------------------ canonical tiled reduction ---
def tile_partial_sums(x: jax.Array) -> jax.Array:
    """Per-tile partial sums [S, ceil(V / RED_TILE)] of ``x`` [S, V]: each
    output element is ``jnp.sum`` over one contiguous RED_TILE-wide tile
    (zero-padded on the right when V is not a tile multiple — exact for the
    mass terms, which are all >= 0 and 0 at masked entries)."""
    s, v = x.shape
    pad = (-v) % RED_TILE
    if pad:
        x = jnp.concatenate([x, jnp.zeros((s, pad), x.dtype)], axis=-1)
    return jnp.sum(x.reshape(s, (v + pad) // RED_TILE, RED_TILE), axis=-1)


def fold_partials(parts: jax.Array) -> jax.Array:
    """Strictly sequential left fold of per-tile partials [S, n] -> [S].
    THE canonical association: ``(((0 + p0) + p1) + ...) + p_{n-1}``. Every
    implementation — oracle, jnp streaming filter, Pallas kernel, the
    LM-head vocab-streaming epilogue, and the tp>1 shard combine (which
    all-gathers per-tile partials and refolds them) — must fold in exactly
    this order to produce the same float."""
    s, n = parts.shape

    def body(i, acc):
        return acc + lax.dynamic_index_in_dim(parts, i, axis=1,
                                              keepdims=False)

    return lax.fori_loop(0, n, body, jnp.zeros((s,), parts.dtype))


def tiled_row_sum(x: jax.Array) -> jax.Array:
    """Canonical row sum [S] of ``x`` [S, V]: RED_TILE partials folded
    sequentially (see :func:`fold_partials`)."""
    return fold_partials(tile_partial_sums(x))


# --------------------------------------------------------------- bit keys ----
def float_to_key(f: jax.Array) -> jax.Array:
    """float32 -> uint32 key, strictly monotone in the float ordering
    (-inf < ... < -0.0 < +0.0 < ... < +inf; NaN patterns land at the ends).
    The fused path bisects this key space instead of sorting."""
    b = lax.bitcast_convert_type(f, jnp.uint32)
    return jnp.where(b >> 31 != 0, ~b, b ^ jnp.uint32(0x80000000))


def key_to_float(k: jax.Array) -> jax.Array:
    """Inverse of :func:`float_to_key`."""
    b = jnp.where(k >> 31 == 0, ~k, k ^ jnp.uint32(0x80000000))
    return lax.bitcast_convert_type(b, jnp.float32)


# ------------------------------------------------- canonical decision math ----
def softmax_mass_stats(lg_k: jax.Array):
    """Unnormalized softmax masses of a (possibly ``-inf``-masked) row:
    ``(U, Z)`` with ``U = exp(lg_k - rowmax)`` (0 at masked entries) and
    ``Z = sum(U)``. Shared verbatim by the oracle and the fused path — the
    nucleus predicate compares these exact floats."""
    m = jnp.max(lg_k, axis=-1)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    u = jnp.exp(lg_k - safe_m[:, None])
    z = tiled_row_sum(u)
    return u, z


def strict_greater_mass(lg_k: jax.Array, u: jax.Array,
                        v: jax.Array) -> jax.Array:
    """``SG(v)`` [S]: total mass of entries strictly above the candidate
    threshold ``v`` [S]. THE nucleus decision predicate's left-hand side;
    every implementation must call this exact reduction."""
    return tiled_row_sum(jnp.where(lg_k > v[:, None], u, 0.0))


def count_ge_key(keys: jax.Array, mid: jax.Array) -> jax.Array:
    """Entries whose bit key is at or above ``mid`` [S] per row — the
    (integer-exact) top-k decision predicate of the bit bisection. Key-space
    comparison keeps the predicate monotone over the whole uint32 domain
    (NaN bit patterns order below ``-inf`` / above ``+inf`` instead of
    poisoning float compares)."""
    return jnp.sum((keys >= mid[:, None]).astype(jnp.int32), axis=-1)


def mass_above_key(keys_k: jax.Array, u: jax.Array,
                   mid: jax.Array) -> jax.Array:
    """``SG`` evaluated in key space [S]: total mass of entries whose bit
    key is strictly above ``mid``. At the key of any present value this sums
    exactly the same ``u`` terms in the same order as
    :func:`strict_greater_mass` (keys are monotone in floats), so the two
    bisections land on thresholds that mask identically — the only
    candidates where the comparisons differ are ``-0.0``/``+0.0``, and IEEE
    compares make those thresholds equivalent as masks."""
    return tiled_row_sum(jnp.where(keys_k > mid[:, None], u, 0.0))


def nucleus_target(top_p: jax.Array, z: jax.Array) -> jax.Array:
    """The nucleus mass target ``T = top_p * Z``, floored so the row
    maximum is always kept (see ``T_FLOOR``)."""
    return jnp.maximum(top_p.astype(jnp.float32) * z, jnp.float32(T_FLOOR))


# ----------------------------------------------------------- sort-based ref ---
def filter_logits_ref(lg: jax.Array, top_k: jax.Array,
                      top_p: jax.Array) -> jax.Array:
    """Apply top-k then nucleus top-p masking to ``lg`` [S, V] via ONE
    descending sort (the oracle the fused kernel must match bit-for-bit).

    ``top_k`` int32 [S] (``<= 0`` disables), ``top_p`` float32 [S]
    (``>= 1`` disables). Returns ``lg`` with dropped entries at ``-inf``.
    """
    s, v = lg.shape
    lg = lg.astype(jnp.float32)
    desc = jnp.sort(lg, axis=-1)[:, ::-1]

    # top-k: the k-th largest value, selected (not computed) — exact
    k = jnp.where(top_k <= 0, v, jnp.minimum(top_k, v))
    kth = jnp.take_along_axis(desc, (k - 1)[:, None], axis=-1)[:, 0]
    lg_k = jnp.where(lg < kth[:, None], -jnp.inf, lg)
    desc_k = jnp.where(desc < kth[:, None], -jnp.inf, desc)

    # top-p: largest rank whose value still satisfies SG(value) < T,
    # found by bisection over ranks of the (masked) descending sort.
    # pred(desc_k[0]) is always true: SG(rowmax) == 0 < T by the floor.
    u, z = softmax_mass_stats(lg_k)
    t = nucleus_target(top_p, z)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + ((hi - lo + 1) >> 1)
        cand = jnp.take_along_axis(desc_k, mid[:, None], axis=-1)[:, 0]
        ok = strict_greater_mass(lg_k, u, cand) < t
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo = jnp.zeros((s,), jnp.int32)
    hi = jnp.full((s,), v - 1, jnp.int32)
    steps = max(1, (v - 1).bit_length())
    lo, _ = lax.fori_loop(0, steps, body, (lo, hi))
    th = jnp.take_along_axis(desc_k, lo[:, None], axis=-1)[:, 0]
    th = jnp.where(top_p >= 1.0, -jnp.inf, th)
    return jnp.where(lg_k < th[:, None], -jnp.inf, lg_k)
