"""Token-choice MoE: sort-based capacity dispatch (drop-on-overflow), or,
when serving a dropless config, every assignment computed.

TPU adaptation notes (DESIGN.md §2): the dispatch is *group-local* — tokens are
sorted into expert buckets independently per batch row, so under [batch -> data]
sharding every gather/scatter stays on-device and the only collectives are the same
row-parallel all-reduces a dense MLP needs (expert FF dims are tensor-sharded on the
model axis). An alternative expert-parallel (experts -> model axis, all-to-all
exchange) implementation lives in ``repro.parallel.expert_parallel`` and is compared
in EXPERIMENTS.md §Perf.

FLOP accounting: capacity padding computes on zero slots; ``core.analytical`` reports
both padded and useful MoE FLOPs (the roofline "useful ratio" catches this).

Held experts: a layer may hold only the first ``MoEConfig.held_count`` of
the routed experts (and under serving tensor parallelism each shard its
slice of those). The router always scores all ``num_experts`` and keeps its
top-k over all of them; the layer computes and combines only the
assignments to the experts it holds, so the shares of a layer held on
different chips add up to the whole layer's output.

Dropless configs are dropless when served (``serve_moe``): every held
assignment goes through the grouped expert kernel. The training forward
(``apply_moe``) keeps capacity dispatch for every config, so its expert
work stays about ``top_k * capacity_factor`` passes a token, as
``core.analytical`` counts it.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, MoEConfig
from ..kernels.moe_experts import ops as experts_ops
from ..parallel.sharding import constrain
from .layers import PyTree, dense_init, silu, gelu


def capacity_per_row(seq: int, moe: MoEConfig) -> int:
    return max(1, math.ceil(seq * moe.top_k * moe.capacity_factor / moe.num_experts))


# ------------------------------------------------------------------------- init ---

def init_moe(key, arch: ArchConfig, dtype=jnp.float32) -> PyTree:
    moe = arch.moe
    assert moe is not None
    d = arch.d_model
    eff = moe.expert_ff or arch.d_ff
    held = moe.num_held
    ks = jax.random.split(key, 8)
    std = 1.0 / math.sqrt(d)
    stdf = 1.0 / math.sqrt(eff)
    p: PyTree = {
        "router": (jax.random.normal(ks[0], (d, moe.num_experts)) * std
                   ).astype(jnp.float32),           # router kept fp32 (numerics)
        "experts": {
            "w1": (jax.random.truncated_normal(ks[1], -2, 2,
                                               (held, d, eff)) * std
                   ).astype(dtype),
            "w3": (jax.random.truncated_normal(ks[2], -2, 2,
                                               (held, d, eff)) * std
                   ).astype(dtype),
            "w2": (jax.random.truncated_normal(ks[3], -2, 2,
                                               (held, eff, d)) * stdf
                   ).astype(dtype),
        },
    }
    if moe.num_shared_experts:
        shared_ff = eff * moe.num_shared_experts
        p["shared"] = {
            "w1": dense_init(ks[4], d, shared_ff, dtype),
            "w3": dense_init(ks[5], d, shared_ff, dtype),
            "w2": dense_init(ks[6], shared_ff, d, dtype),
        }
    return p


# --------------------------------------------------------------------- routing ---

def route(logits: jax.Array, moe: MoEConfig) -> Tuple[jax.Array, jax.Array]:
    """Router logits [..., E] fp32 -> (top-k weights, top-k expert ids): a
    softmax over all E experts, the k most probable kept, their
    probabilities renormalised to sum to 1 unless ``moe.norm_topk`` is off
    (then they are the raw probabilities)."""
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    return top_w, top_ids


def held_offset(local_e: int, tp_axis: Optional[str]):
    """Global id of the first expert this layer (or tp shard) holds: 0, or
    the start of the shard's slice under tp."""
    if tp_axis is None:
        return 0
    return jax.lax.axis_index(tp_axis) * local_e


def expert_counts(hits: jax.Array) -> jax.Array:
    """Assignments per held expert [E] -> int32 [2]: (token-to-held-expert
    assignments, held experts with at least one)."""
    return jnp.stack([jnp.sum(hits), jnp.sum(hits > 0)]).astype(jnp.int32)


# ------------------------------------------------------------------ sort dispatch -

def _route_indices(logits: jax.Array, moe: MoEConfig, capacity: int,
                   eff_capacity: Optional[jax.Array] = None):
    """Per-batch-row routing *index* math (cheap int ops; vmapped over rows).

    logits [S, E] fp32 -> (st [S*k] source token ids, sw [S*k] weights,
    slot [S*k] capacity-slot ids incl. overflow sentinel, valid [S*k]).

    ``capacity`` sizes the dispatch buffer (static); ``eff_capacity`` — a
    traced scalar — optionally *tightens* the drop threshold below it. The
    chunked-prefill path passes the full prompt's capacity here so a prompt
    served in one padded chunk reproduces the static engine's drop pattern
    exactly: the chunk's trailing padding cannot displace real tokens (the
    stable expert sort keeps padded entries after every real one), but the
    padded shape would otherwise inflate the capacity bucket.
    """
    s, e = logits.shape
    top_w, top_ids = route(logits, moe)                       # [S, k]

    flat_e = top_ids.reshape(-1)                              # [S*k]
    flat_t = jnp.repeat(jnp.arange(s), moe.top_k)             # [S*k]
    flat_w = top_w.reshape(-1)

    order = jnp.argsort(flat_e)                               # stable
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    start = jnp.searchsorted(se, jnp.arange(e), side="left")  # [E]
    pos = jnp.arange(s * moe.top_k) - start[se]
    limit = capacity if eff_capacity is None \
        else jnp.minimum(capacity, eff_capacity)
    valid = pos < limit
    slot = jnp.where(valid, se * capacity + pos, e * capacity)
    return st, sw, slot, valid


def apply_moe(arch: ArchConfig, p: PyTree, x: jax.Array,
              tp_axis: Optional[str] = None,
              eff_capacity: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar): the training forward.

    ``tp_axis``: serving tensor parallelism — the call runs inside
    ``shard_map`` on a shard holding ``num_held / tp`` contiguous experts
    (leading axis of ``p["experts"]``), the router and activations
    replicated. Routing stays global (every shard sees the full top-k over
    all E experts); each shard dispatches, computes, and combines only the
    assignments of the experts it holds, and the partial combines meet in
    one fp32 psum — the MoE layer's single collective, in place of the dense
    MLP's row-parallel reduce.

    ``eff_capacity`` (traced scalar): tightens the per-row drop threshold
    below the buffer capacity. The chunked-prefill path passes the full
    prompt's ``capacity_per_row`` so a single-chunk prompt drops exactly the
    tokens the static engine's full-prompt dispatch would drop, instead of
    a bucket inflated by the chunk's padded shape.

    Capacity dispatch for every config, dropless ones included: dropless
    is a serving setting (``serve_moe``).
    """
    moe = arch.moe
    with jax.named_scope("moe"):
        y, logits, _ = _apply_moe_inner(arch, p, x, tp_axis, eff_capacity,
                                        None, dropless=False)
        # Switch-style load-balancing aux loss: E * sum_e f_e * P_e
        probs = jax.nn.softmax(logits, axis=-1)               # [B,S,E] fp32
        top1 = jnp.argmax(probs, axis=-1)
        f = jnp.mean(jax.nn.one_hot(top1, moe.num_experts, dtype=jnp.float32),
                     axis=(0, 1))
        pmean = jnp.mean(probs, axis=(0, 1))
        aux = moe.num_experts * jnp.sum(f * pmean) * moe.aux_loss_weight
        return y, aux


def serve_moe(arch: ArchConfig, p: PyTree, x: jax.Array,
              tp_axis: Optional[str] = None,
              eff_capacity: Optional[jax.Array] = None,
              row_mask: Optional[jax.Array] = None
              ) -> Tuple[jax.Array, jax.Array]:
    """The serving forward: ``apply_moe`` without the aux loss, and a
    dropless config's held assignments, every one, through the grouped
    expert kernel.
    ``row_mask`` [B, S] (optional) marks the rows that hold a token; the
    others (empty decode slots, a chunk's padding) route nowhere. Returns
    ``(y, counts)`` with ``counts`` int32 [2]: the token-to-held-expert
    assignments computed and the held experts with at least one (summed
    over tp shards)."""
    with jax.named_scope("moe"):
        y, _, counts = _apply_moe_inner(arch, p, x, tp_axis, eff_capacity,
                                        row_mask, dropless=arch.moe.dropless)
        return y, counts


def _apply_moe_inner(arch, p, x, tp_axis, eff_capacity, row_mask, dropless):
    """-> (y, router logits, counts)."""
    logits = (x.astype(jnp.float32) @ p["router"])            # [B, S, E]
    if dropless:
        y, hits = _dropless(arch, p, x, logits, tp_axis, row_mask)
    else:
        y, hits = _capacity(arch, p, x, logits, tp_axis, eff_capacity,
                            row_mask)
    if "shared" in p:
        # shared experts are a dense MLP: under tp_axis their weights are the
        # usual Megatron column/row shards, and the row-parallel partial sum
        # rides the same psum as the routed combine below
        sh = p["shared"]
        hs = silu(x @ sh["w1"].astype(x.dtype)) * (x @ sh["w3"].astype(x.dtype))
        y = y + hs @ sh["w2"].astype(x.dtype)
    counts = expert_counts(hits)
    if tp_axis is not None:
        y = jax.lax.psum(y.astype(jnp.float32), tp_axis).astype(x.dtype)
        counts = jax.lax.psum(counts, tp_axis)
    return y, logits, counts


def _dropless(arch, p, x, logits, tp_axis, row_mask):
    """Every assignment to a held expert computed; -> (y, hits [E_local])."""
    moe = arch.moe
    assert arch.mlp == "swiglu", (arch.name, "dropless experts are SwiGLU")
    b, s, d = x.shape
    w = p["experts"]
    local_e = w["w1"].shape[0]
    top_w, top_ids = route(logits, moe)                       # [B, S, k]
    ids = top_ids - held_offset(local_e, tp_axis)
    if row_mask is not None:
        ids = jnp.where(row_mask[..., None], ids, local_e)
    ids = ids.reshape(b * s, moe.top_k).astype(jnp.int32)
    y = experts_ops.moe_experts(x.reshape(b * s, d), ids,
                                top_w.reshape(b * s, moe.top_k),
                                w["w1"], w["w3"], w["w2"])
    return y.reshape(b, s, d), experts_ops.group_sizes(ids, local_e)


def _capacity(arch, p, x, logits, tp_axis, eff_capacity, row_mask):
    """Capacity dispatch (drop-on-overflow); -> (y, hits [E_local]): the
    kept assignments of the rows in ``row_mask``."""
    moe = arch.moe
    b, s, d = x.shape
    cap = capacity_per_row(s, moe)
    w = p["experts"]
    local_e = w["w1"].shape[0]          # experts this shard holds

    st, sw, slot, valid = jax.vmap(
        lambda lr: _route_indices(lr, moe, cap, eff_capacity))(logits)
    if tp_axis is not None or moe.held_count:
        # held experts: rebase global capacity-slot ids onto the experts
        # held here; slots held elsewhere fold into the overflow sentinel so
        # they neither dispatch nor combine here
        off = held_offset(local_e, tp_axis) * cap
        slot = slot - off
        valid = valid & (slot >= 0) & (slot < local_e * cap)
        slot = jnp.where(valid, slot, local_e * cap)

    def dispatch_row(xr, st_r, slot_r, valid_r):
        gathered = xr[st_r] * valid_r[:, None].astype(xr.dtype)   # [S*k, D]
        slots_r = jnp.zeros((local_e * cap + 1, d), xr.dtype)
        slots_r = slots_r.at[slot_r].add(gathered)
        return slots_r[:-1].reshape(local_e, cap, d)

    slots = jax.vmap(dispatch_row)(x, st, slot, valid)        # [B, El, C, D]

    # expert parallelism (training/pjit path): slots all-to-all from
    # [B->data] row-local layout into [E->model] expert-owner layout; each
    # device runs its E/16 experts' GEMMs. (Identity inside shard_map.)
    slots = constrain(slots, "batch", "experts", None, None)
    act = silu if arch.mlp == "swiglu" else gelu
    h = act(jnp.einsum("becd,edf->becf", slots, w["w1"].astype(x.dtype)))
    if arch.mlp == "swiglu":
        h = h * jnp.einsum("becd,edf->becf", slots, w["w3"].astype(x.dtype))
    h = constrain(h, "batch", "experts", None, None)
    out = jnp.einsum("becf,efd->becd", h, w["w2"].astype(x.dtype))
    out = constrain(out, "batch", "experts", None, None)

    def combine_row(out_r, st_r, sw_r, slot_r, valid_r):
        flat = jnp.concatenate(
            [out_r.reshape(local_e * cap, d), jnp.zeros((1, d), out_r.dtype)],
            0)
        contrib = flat[slot_r] * (sw_r * valid_r).astype(out_r.dtype)[:, None]
        y_r = jnp.zeros((s, d), out_r.dtype)
        return y_r.at[st_r].add(contrib)

    y = jax.vmap(combine_row)(out, st, sw, slot, valid)
    y = constrain(y, "batch", "seq", None)

    kept = valid
    if row_mask is not None:
        kept = kept & jnp.take_along_axis(row_mask, st, axis=1)
    hits = jnp.sum(jax.nn.one_hot(slot // cap, local_e, dtype=jnp.int32)
                   * kept[..., None], axis=(0, 1))
    return y, hits
