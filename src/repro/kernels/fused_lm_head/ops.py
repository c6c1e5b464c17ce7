"""Vocab-streaming LM-head epilogue: hidden state [S, D] -> sampled int32
token [S] without ever materializing the ``[S, V]`` logits.

The unembed GEMM is tiled over vocab blocks (``ref.gemm_tile``); every
statistic the epilogue needs — the greedy argmax, the sanitizer's all-finite
probe, the top-k/top-p bisection predicates of ``kernels.fused_sampling``,
the softmax masses, and the inverse-CDF draw's prefix walk — is carried
across tiles in ``[S]``- or ``[S, V / RED_TILE]``-sized accumulators. Logit
tiles are *recomputed* per bisection sweep rather than stored: the whole
point is that HBM never holds a row of logits, and on the accelerator the
weight tile reads are the traffic the paper says we already pay once.

Bit-identity with the full-logits oracle (``ref.head_epilogue``) is by
construction, not tolerance:

* tiled GEMM == full GEMM under jit (the convert folds into the dot either
  way, so per-element logits match bitwise);
* integer predicates (top-k counts, argmax/first-hit index compares) are
  order-exact;
* every float mass is summed as the canonical RED_TILE partials folded
  left-to-right (``fused_sampling.ref``), and the draw's within-tile prefix
  is ``ref.tile_cumsum`` in every implementation.

Tensor-parallel (``axis_name`` set): each shard slices its own contiguous
vocab columns from the REPLICATED head weight (the sharding layer keeps
embedding/head/norms replicated — see ``parallel/sharding.py``), sweeps its
slice, and the shards combine carried statistics, never logits: integer
psums for the top-k counts, an all-gather of (max, argmax-candidate, probe)
triples, and all-gathers of the per-RED_TILE-tile mass partials ``[S,
V / tp / RED_TILE]`` which every shard refolds in canonical global tile
order. (A psum of per-shard folded totals would NOT be bit-exact — float
folds do not reassociate — which is why partials cross the wire instead.)
Requires ``(V / tp) % RED_TILE == 0`` so shard boundaries land on canonical
tile boundaries; the engine checks :func:`tp_fusable` and serves the
unfused path otherwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..fused_sampling import ops as sops
from ..fused_sampling import ref as sref
from . import kernel, ref

RED_TILE = sref.RED_TILE
BISECT_STEPS = sops.BISECT_STEPS
TOP_KEY = sops.TOP_KEY
_INT_MAX = jnp.int32(2 ** 31 - 1)


def supported() -> bool:
    return jax.default_backend() == "tpu"


def tp_fusable(vocab: int, tp: int) -> bool:
    """Whether the fused head can serve this (padded) vocab at this tp:
    shard slices must be whole numbers of canonical reduction tiles."""
    return tp <= 1 or (vocab % tp == 0 and (vocab // tp) % RED_TILE == 0)


def head_tokens(x: jax.Array, w: jax.Array, rs: jax.Array, temps: jax.Array,
                top_k: jax.Array, top_p: jax.Array, *, sampled: bool,
                filtered: bool, softcap=None, axis_name=None, tp: int = 1,
                interpret: bool = False):
    """Fused unembed + sample: ``x`` [S, D] hidden, ``w`` [D, V] head weight
    (model dtype, REPLICATED under tp) -> ``(tokens int32 [S], ok bool [S])``
    with ``ok`` the per-row all-finite probe of the raw logits.

    ``rs`` float32 [S] are the draw uniforms (``ref.row_uniforms``); rows
    with ``temps == 0`` take the raw-logits argmax. ``sampled`` / ``filtered``
    are the engine's static jit-variant flags. Dispatches to the Pallas
    kernel on TPU (or under ``interpret``) for the single-shard case; the
    jnp streaming path is the production path elsewhere and under tp > 1.
    """
    if axis_name is None and (supported() or interpret):
        return kernel.head_tokens(x, w, rs, temps, top_k, top_p,
                                  sampled=sampled, filtered=filtered,
                                  softcap=softcap, interpret=interpret)
    return _head_tokens_jnp(x, w, rs, temps, top_k, top_p, sampled=sampled,
                            filtered=filtered, softcap=softcap,
                            axis_name=axis_name, tp=tp)


def _head_tokens_jnp(x, w, rs, temps, top_k, top_p, *, sampled, filtered,
                     softcap, axis_name, tp):
    s, _ = x.shape
    v_total = w.shape[1]
    shard_tp = axis_name is not None and tp > 1
    if shard_tp:
        assert tp_fusable(v_total, tp), (v_total, tp)
        v_local = v_total // tp
        shard = lax.axis_index(axis_name)
        w = lax.dynamic_slice_in_dim(w, shard * v_local, v_local, axis=1)
        offset = (shard * v_local).astype(jnp.int32)
    else:
        v_local = v_total
        offset = jnp.int32(0)
    t_w = ref.gemm_tile(v_local)
    n_tiles = v_local // t_w
    n128 = -(-v_local // RED_TILE)          # local canonical tiles
    wx = w.astype(x.dtype)

    def logits_tile(t):
        wt = lax.dynamic_slice_in_dim(wx, t * t_w, t_w, axis=1)
        lt = (x @ wt).astype(jnp.float32)
        if softcap:
            lt = softcap * jnp.tanh(lt / softcap)
        return lt

    # ---- sweep 1: raw-logits running max, first-occurrence argmax, probe --
    def max_body(t, carry):
        m, am, ok = carry
        lt = logits_tile(t)
        tm = jnp.max(lt, axis=-1)
        ta = jnp.argmax(lt, axis=-1).astype(jnp.int32) + t * t_w + offset
        return (jnp.maximum(m, tm), jnp.where(tm > m, ta, am),
                ok & jnp.all(jnp.isfinite(lt), axis=-1))

    m_raw, am, ok = lax.fori_loop(
        0, n_tiles, max_body,
        (jnp.full((s,), -jnp.inf, jnp.float32),
         jnp.full((s,), offset, jnp.int32), jnp.ones((s,), bool)))

    if shard_tp:
        vals = lax.all_gather(m_raw, axis_name)            # [tp, S]
        idxs = lax.all_gather(am, axis_name)
        m_raw = jnp.max(vals, axis=0)                      # max is exact
        # first global occurrence = min index among shards hitting the max
        am = jnp.min(jnp.where(vals == m_raw[None, :], idxs, _INT_MAX),
                     axis=0)
        ok = jnp.all(lax.all_gather(ok, axis_name), axis=0)
    if not sampled:
        return am, ok

    # ---- scaled domain (division by a positive is monotone, so the scaled
    # row max is exactly the raw max divided — no extra sweep) ----
    temps = temps.astype(jnp.float32)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    m_scaled = m_raw / safe_t
    safe_m = jnp.where(jnp.isfinite(m_scaled), m_scaled, 0.0)

    def scaled_tile(t):
        return logits_tile(t) / safe_t[:, None]

    def count_ge(mid):
        def body(t, c):
            keys = sref.float_to_key(scaled_tile(t))
            return c + jnp.sum((keys >= mid[:, None]).astype(jnp.int32),
                               axis=-1)
        cnt = lax.fori_loop(0, n_tiles, body, jnp.zeros((s,), jnp.int32))
        return lax.psum(cnt, axis_name) if shard_tp else cnt

    def mass_parts(tile_fn, mid=None):
        """Local per-RED_TILE-tile partial masses [S, n128] of
        ``exp(tile - safe_m)``, optionally masked to keys > mid."""
        def body(t, parts):
            lt = tile_fn(t)
            ut = jnp.exp(lt - safe_m[:, None])
            if mid is not None:
                ut = jnp.where(sref.float_to_key(lt) > mid[:, None], ut, 0.0)
            sub = sref.tile_partial_sums(ut)
            return lax.dynamic_update_slice_in_dim(
                parts, sub, t * sub.shape[1], axis=1)
        return lax.fori_loop(0, n_tiles, body,
                             jnp.zeros((s, n128), jnp.float32))

    def fold_global(parts_local):
        """Canonical global fold of local partials; under tp the shards
        gather each other's partials and every shard refolds the full
        sequence in global tile order — bit-exact at any tp."""
        if shard_tp:
            g = lax.all_gather(parts_local, axis_name)     # [tp, S, n128]
            parts = jnp.transpose(g, (1, 0, 2)).reshape(s, -1)
        else:
            parts = parts_local
        return parts, sref.fold_partials(parts)

    # ---- top-k: the same 32-step bit-key count bisection as the filter ----
    if filtered:
        k = jnp.where(top_k <= 0, v_total, jnp.minimum(top_k, v_total))

        def kth_step(_, lohi):
            lo, hi = lohi
            mid = lo + ((hi - lo + jnp.uint32(1)) >> 1)
            take = count_ge(mid) >= k
            return (jnp.where(take, mid, lo),
                    jnp.where(take, hi, mid - jnp.uint32(1)))

        lo, _ = lax.fori_loop(0, BISECT_STEPS, kth_step,
                              (jnp.zeros((s,), jnp.uint32),
                               jnp.full((s,), TOP_KEY, jnp.uint32)))
        kth = sref.key_to_float(lo)

        def masked_tile(t):
            lt = scaled_tile(t)
            return jnp.where(lt < kth[:, None], -jnp.inf, lt)
    else:
        masked_tile = scaled_tile

    # ---- top-p: the same 32-step mass bisection, masses refolded from
    # carried partials each step ----
    if filtered:
        _, z = fold_global(mass_parts(masked_tile))
        t_nuc = sref.nucleus_target(top_p, z)

        def topp_step(_, lohi):
            lo, hi = lohi
            mid = lo + ((hi - lo) >> 1)
            _, sg = fold_global(mass_parts(masked_tile, mid))
            take = sg < t_nuc
            return (jnp.where(take, lo, mid + jnp.uint32(1)),
                    jnp.where(take, mid, hi))

        _, hi = lax.fori_loop(0, BISECT_STEPS, topp_step,
                              (jnp.zeros((s,), jnp.uint32),
                               jnp.full((s,), TOP_KEY, jnp.uint32)))
        th = sref.key_to_float(hi)
        th = jnp.where(top_p >= 1.0, -jnp.inf, th)

        def final_tile(t):
            lt = masked_tile(t)
            return jnp.where(lt < th[:, None], -jnp.inf, lt)
    else:
        final_tile = masked_tile

    # ---- inverse-CDF draw: Z from carried partials, then the prefix walk
    # (ref.draw_tokens step 5, with the entering accs precomputed by the
    # identical sequential adds so the tp shards can walk their slices) ----
    parts_g, zprime = fold_global(mass_parts(final_tile))
    target = rs.astype(jnp.float32) * zprime
    n_global = parts_g.shape[1]

    def acc_body(i, accs):
        prev = lax.dynamic_index_in_dim(accs, i, axis=1, keepdims=False)
        part = lax.dynamic_index_in_dim(parts_g, i, axis=1, keepdims=False)
        return lax.dynamic_update_slice_in_dim(
            accs, (prev + part)[:, None], i + 1, axis=1)

    accs = lax.fori_loop(0, n_global - 1, acc_body,
                         jnp.zeros((s, n_global), jnp.float32))
    local_base = (offset // RED_TILE).astype(jnp.int32)

    def hit_body(t, tok):
        u3 = ref.pad_tiles(jnp.exp(final_tile(t) - safe_m[:, None]))
        t128 = u3.shape[1]

        def sub_body(j, tok):
            g = t * t128 + j + local_base                # global 128-tile
            acc = lax.dynamic_index_in_dim(accs, g, axis=1, keepdims=False)
            tile = lax.dynamic_index_in_dim(u3, j, axis=1, keepdims=False)
            cs = acc[:, None] + ref.tile_cumsum(tile)
            hit = cs > target[:, None]
            idx = (jnp.argmax(hit, axis=-1).astype(jnp.int32)
                   + g.astype(jnp.int32) * RED_TILE)
            return jnp.where((tok < 0) & jnp.any(hit, axis=-1), idx, tok)

        return lax.fori_loop(0, t128, sub_body, tok)

    tok = lax.fori_loop(0, n_tiles, hit_body, jnp.full((s,), -1, jnp.int32))
    if shard_tp:
        g = lax.all_gather(tok, axis_name)
        tok = jnp.min(jnp.where(g < 0, _INT_MAX, g), axis=0)
        tok = jnp.where(tok == _INT_MAX, -1, tok)
    drawn = jnp.where(tok < 0, 0, tok)
    return jnp.where(temps > 0, drawn, am).astype(jnp.int32), ok
