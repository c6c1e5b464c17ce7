"""Logical-axis sharding rules (MaxText-style) + param/batch/cache PartitionSpecs.

Design decisions (see DESIGN.md §4):

* **Feature-dim tensor parallelism.** Query-head counts in the assigned grid (24, 40,
  12...) are not divisible by the 16-way model axis, and JAX rejects uneven input
  shardings. All projection weights are therefore sharded on their *fused feature*
  dimensions (q_dim, kv_dim, d_ff, ssm inner), which are multiples of 16 for every
  arch; GSPMD propagates (and pads) the derived head-dim shardings of intermediate
  activations on its own.

* **Sequence parallelism.** The residual stream between blocks is sharded
  [batch->data, seq->model]. Megatron-SP falls out of GSPMD propagation: all-gather
  into the TP GEMMs, reduce-scatter back — and live activations per device drop 16x,
  which is what lets 88-layer train_4k cells fit 16 GB HBM.

* **Decode KV caches are sharded on the cache-length axis** (S/16 per device): the
  only collectives decode attention needs are then tiny [B,H,1] softmax-stat
  all-reduces and one [B,H,D] output all-reduce, while cache bytes scale 1/256 over
  the pod. (Head-count sharding is illegal for kv=8<16; head_dim sharding would
  all-reduce full score tensors.)

Rules are looked up by *leaf path name* of the parameter pytree — parameter naming in
``repro.models`` is the contract.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rules = Dict[str, Optional[object]]

_state = threading.local()


# ------------------------------------------------------------------------ rules ---

def make_rules(multi_pod: bool = False, *, seq_parallel: bool = True,
               fsdp: bool = True, expert_parallel: bool = True,
               overrides: Sequence[Tuple[str, Optional[str]]] = ()) -> Rules:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    rules: Rules = {
        # ---- activations ----
        "batch": batch_axes,
        "seq": "model" if seq_parallel else None,     # sequence-parallel residual
        "cache_seq": "model",                         # decode KV cache length
        "embed": None,
        # attention intermediates: q-head dim sharded on model (uneven counts are
        # padded by GSPMD — legal for intermediates, not for jit inputs)
        "q_heads": "model",
        "kv": None,
        "vocab": "model",                             # logits vocab axis
        # ---- parameters ----
        # fsdp: weight-matrix dim sharded over the data axis (ZeRO-3-style weight
        # streaming; params are bf16 so the per-layer all-gather is halved).
        "fsdp": None if not fsdp else "data",
        "tensor": "model",                            # Megatron TP feature dims
        # experts shard over the *model* axis (E: 128/64/16 all divide 16); the
        # per-expert FF dim stays unsharded. GSPMD then moves capacity slots
        # [B->data, E, C, D] to [B->data, E->model, C, D] with an all-to-all over
        # model — classic expert parallelism expressed in pjit. (E over the data
        # axis would fight the batch sharding and re-lay out every MoE layer.)
        "experts": "model" if expert_parallel else None,
        "expert_mlp": None if expert_parallel else "model",
        "opt_flat": ("data", "model"),                # ZeRO-1 optimizer states
        "none": None,
    }
    for name, axis in overrides:
        rules[name] = axis
    return rules


def activate(mesh: Mesh, rules: Rules):
    """Context manager: make (mesh, rules) current for spec()/constrain()."""
    @contextlib.contextmanager
    def _ctx():
        prev = getattr(_state, "ctx", None)
        _state.ctx = (mesh, rules)
        # the mesh is also ambient for sharding-in-types
        try:
            with jax.set_mesh(mesh):
                yield
        finally:
            _state.ctx = prev
    return _ctx()


def current() -> Optional[Tuple[Mesh, Rules]]:
    return getattr(_state, "ctx", None)


def spec(*logical: Optional[str]) -> P:
    ctx = current()
    if ctx is None:
        return P(*([None] * len(logical)))
    _, rules = ctx
    return P(*[rules.get(l) if l else None for l in logical])


def sharding(*logical: Optional[str]) -> Optional[NamedSharding]:
    ctx = current()
    if ctx is None:
        return None
    mesh, _ = ctx
    return NamedSharding(mesh, spec(*logical))


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint if a mesh is active, else identity."""
    s = sharding(*logical)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


# ----------------------------------------------------------------- param specs ----

# leaf name -> logical axes of the *trailing* dims (leading scan axis padded None).
# Matrices are (fsdp x tensor) sharded: column-parallel weights put their output
# feature dim on "tensor", row-parallel their input dim; the other big dim streams
# over "fsdp". Every "tensor"/"fsdp" dim is a multiple of 16 for all archs.
_PARAM_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "embedding": ("tensor", "fsdp"),     # [V, D] vocab-sharded
    "pos_embedding": (None, None),
    "head": ("fsdp", "tensor"),          # [D, V]
    "wqkv": ("fsdp", "tensor"),
    "wq": ("fsdp", "tensor"),
    "wk": ("fsdp", "tensor"),
    "wv": ("fsdp", "tensor"),
    "wo": ("tensor", "fsdp"),
    "bqkv": ("tensor",),
    "bq": ("tensor",),
    "bk": ("tensor",),
    "bv": ("tensor",),
    "bo": (None,),
    "w1": ("fsdp", "tensor"),
    "w3": ("fsdp", "tensor"),
    "w2": ("tensor", "fsdp"),
    "b1": ("tensor",),
    "b3": ("tensor",),
    "b2": (None,),
    "router": ("fsdp", None),
    "in_proj": ("fsdp", "tensor"),
    "out_proj": ("tensor", "fsdp"),
    "conv": (None, "tensor"),
    "A_log": (None,),
    "D": (None,),
    "dt_bias": (None,),
    "norm_scale": (None,),
    "scale": (None,),
    "bias": (None,),
    "dense": ("fsdp", None),
}

# under an "experts" parent the matrices carry a leading expert dim:
# E -> model (expert parallelism), D -> data (FSDP weight streaming). The
# per-expert FF dim stays whole so each expert's GEMM runs on its owner shard.
_EXPERT_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "w1": ("experts", "fsdp", None),
    "w3": ("experts", "fsdp", None),
    "w2": ("experts", None, "fsdp"),
}


def _leaf_spec(path: Tuple[str, ...], leaf) -> P:
    name = path[-1]
    in_experts = "experts" in path[:-1]
    table = _EXPERT_RULES if (in_experts and name in _EXPERT_RULES) else _PARAM_RULES
    if name not in table:
        raise KeyError(f"no sharding rule for parameter {'/'.join(path)}")
    logical = table[name]
    pad = leaf.ndim - len(logical)
    assert pad >= 0, (path, leaf.shape, logical)
    return spec(*([None] * pad + list(logical)))


def _path_names(key_path) -> Tuple[str, ...]:
    names = []
    for k in key_path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def param_pspecs(params) -> object:
    """PartitionSpec pytree mirroring a parameter pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: _leaf_spec(_path_names(kp), leaf), params)


def param_shardings(params, mesh: Mesh) -> object:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), param_pspecs(params),
                        is_leaf=lambda s: isinstance(s, P))


# ----------------------------------------------------------- batch / cache specs --

def batch_pspecs(batch: Dict[str, jax.Array]) -> Dict[str, P]:
    """Input batches: leading batch dim -> data(+pod); everything else replicated."""
    out = {}
    for name, v in batch.items():
        if name == "mrope_positions":        # [3, B, S]
            out[name] = spec(None, "batch", None)
        elif v.ndim >= 1:
            out[name] = spec(*(["batch"] + [None] * (v.ndim - 1)))
        else:
            out[name] = P()
    return out


def opt_state_pspecs(state, params_specs, zero1: bool) -> object:
    """Optimizer-state specs.

    zero1: flat [Z, padded] leaves fully sharded over (data, model) — ZeRO-1.
    else : m/v mirror the parameter specs (data-replicated, the paper-faithful
           baseline whose 4x-model-size LAMB traffic Takeaway 8 measures).
    """
    ctx = current()
    rules = dict(ctx[1]) if ctx else {}
    # ZeRO sharding stays within one pod (DCN all-gathers per step would dominate)
    flat_axes = rules.get("opt_flat", ("data", "model"))
    expert_axis = rules.get("experts")

    def flat_spec(key_path, leaf):
        names = _path_names(key_path)
        if "experts" in names and leaf.ndim == 3:
            # [Z, E, flat]: expert dim keeps its model sharding; flat over data
            return P(None, expert_axis, "data")
        if leaf.ndim == 2 and "experts" in names:
            return P(expert_axis, "data")
        return P(*([None] * (leaf.ndim - 1) + [flat_axes]))

    out = {}
    for k, v in state.items():
        if k == "step":
            out[k] = P()
        elif zero1:
            out[k] = jax.tree_util.tree_map_with_path(flat_spec, v)
        else:
            out[k] = params_specs
    return out


def _sanitize(spec: P, shape: Tuple[int, ...], axis_sizes) -> P:
    out = []
    for i, axes in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if axes is None:
            out.append(None)
            continue
        axes_t = axes if isinstance(axes, tuple) else (axes,)
        kept = []
        size = 1
        for a in axes_t:
            s = axis_sizes[a]
            if shape[i] % (size * s) == 0:
                kept.append(a)
                size *= s
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    return P(*out)


def sanitize_spec(spec: P, shape: Tuple[int, ...]) -> P:
    """Drop mesh axes from dims they don't divide (jit inputs must divide
    evenly — e.g. the batch axis on global_batch=1 long-context cells)."""
    ctx = current()
    if ctx is None:
        return spec
    mesh, _ = ctx
    return _sanitize(spec, shape, mesh.shape)


def sanitize_tree(specs, structs):
    return jax.tree.map(
        lambda s, x: sanitize_spec(s, x.shape), specs, structs,
        is_leaf=lambda s: isinstance(s, P))


def flat_grad_pspec(key_path, leaf) -> P:
    """Spec for a flat-layout (ZeRO-2 style) gradient-accumulation leaf."""
    ctx = current()
    rules = dict(ctx[1]) if ctx else {}
    names = _path_names(key_path)
    if "experts" in names and leaf.ndim == 3:
        return P(None, rules.get("experts"), "data")
    flat_axes = rules.get("opt_flat", ("data", "model"))
    return P(*([None] * (leaf.ndim - 1) + [flat_axes]))


def constrain_flat(tree) -> object:
    """Constrain a flat-layout grad tree to its ZeRO sharding."""
    if current() is None:
        return tree
    mesh, _ = current()
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, flat_grad_pspec(kp, leaf))), tree)


# -------------------------------------------------------- serving TP specs ----

# Megatron tensor-parallel layout for the *serving* engine's shard_map path
# (replicated activations, head-sharded attention, column/row-parallel MLP).
# Unlike the training rules above these name the mesh axis directly — the
# serving mesh is a fixed 1-D ("model",) mesh, there is no logical-rule
# indirection to thread through shard_map's in_specs. Biases of row-parallel
# projections (bo, b2) stay replicated: they are added once, AFTER the psum.
_SERVING_TP_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "wq": (None, "model"),      # column-parallel: each shard owns Hq/tp heads
    "wk": (None, "model"),      # (contiguous head blocks — q/kv dims are
    "wv": (None, "model"),      #  head-major, so block i == heads of shard i)
    "bq": ("model",),
    "bk": ("model",),
    "bv": ("model",),
    "wo": ("model", None),      # row-parallel: partial sums -> psum
    "w1": (None, "model"),      # column-parallel d_ff
    "w3": (None, "model"),
    "b1": ("model",),
    "b3": ("model",),
    "w2": ("model", None),      # row-parallel: partial sums -> psum
}

# under an "experts" parent the matrices carry a leading [E, ...] expert dim:
# E -> model (expert parallelism), the per-expert GEMM dims whole — each
# shard owns E/tp complete experts and the combine meets in one psum.
# ("shared" experts are a plain dense MLP and take the column/row rules.)
_SERVING_EXPERT_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "w1": ("model", None, None),
    "w3": ("model", None, None),
    "w2": ("model", None, None),
}


def serving_param_pspecs(params) -> object:
    """PartitionSpec pytree for the TP serving engine (shard_map in_specs).

    Attention/MLP projections follow ``_SERVING_TP_RULES``; routed-expert
    weights shard E-major per ``_SERVING_EXPERT_RULES``; every other leaf —
    embedding, lm head, norms, router, mamba mixers, row-parallel biases —
    is replicated, so the logits (and therefore the sampler's draws) are
    computed identically on every shard and the emitted token vector needs
    no collective at all. Fused ``wqkv``/``bqkv`` leaves are rejected: a
    contiguous slice of the fused feature dim would mix q and kv columns —
    the engine splits them into wq/wk/wv before sharding
    (``serving.engine._split_fused_qkv``).
    """
    def leaf_spec(key_path, leaf):
        names = _path_names(key_path)
        name = names[-1]
        if name in ("wqkv", "bqkv"):
            raise ValueError(
                "fused qkv cannot be head-sharded; split into wq/wk/wv first "
                f"({'/'.join(names)})")
        if "experts" in names[:-1] and name in _SERVING_EXPERT_RULES:
            logical = _SERVING_EXPERT_RULES[name]
        else:
            logical = _SERVING_TP_RULES.get(name)
        if logical is None:
            return P(*([None] * leaf.ndim))
        pad = leaf.ndim - len(logical)
        assert pad >= 0, (key_path, leaf.shape, logical)
        return P(*([None] * pad + list(logical)))
    return jax.tree_util.tree_map_with_path(leaf_spec, params)


# leaf names of the serving decode-state tree — the one definition shared by
# the pspec builder here and the engine's CoW page copy / KV-head-replication
# transforms (a new paged layer kind must extend these, nowhere else)
PAGED_STATE_LEAVES = ("k", "v")         # per-page KV pools [P, page, Hkv, Dh]
SLOT_STATE_LEAVES = ("conv", "state")   # per-slot mamba state


def paged_pool_pspecs(pools) -> object:
    """Shard the engine's per-layer decode state for TP serving.

    Attention page pools (``PAGED_STATE_LEAVES``, [P, page, Hkv, Dh];
    scanned stacks carry a leading period axis) shard the KV-head axis —
    always ndim-2 — on "model". Page ids stay global: each shard holds the
    same pages, 1/tp of every page's heads, so one host allocator/page
    table drives all shards. Mamba slot-state leaves (``SLOT_STATE_LEAVES``)
    stay replicated: the mixer's weights are replicated, every shard
    advances the identical recurrence, and the state is too small to be
    worth the collectives sharding it would cost."""
    def leaf_spec(key_path, leaf):
        name = _path_names(key_path)[-1]
        if name in PAGED_STATE_LEAVES:
            spec = [None] * leaf.ndim
            spec[-2] = "model"
            return P(*spec)
        if name in SLOT_STATE_LEAVES:
            return P(*([None] * leaf.ndim))
        raise KeyError(f"no serving-state sharding rule for "
                       f"{'/'.join(_path_names(key_path))}")
    return jax.tree_util.tree_map_with_path(leaf_spec, pools)


def shard_map_tp(f, mesh: Mesh, in_specs, out_specs):
    """``shard_map`` with varying-manual-axes checking off.

    The TP serving steps return psum-replicated values (token ids) under a
    ``P()``/``P(None)`` out_spec; the checker cannot always prove that
    through the sampler's PRNG ops."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


def _cache_leaf_spec(path: Tuple[str, ...], leaf) -> P:
    name = path[-1]
    if name in ("k", "v", "cross_k", "cross_v"):
        # [(periods,)] B, S, Hkv, Dh — shard the cache-length axis on model
        logical = ("batch", "cache_seq", None, None)
    elif name == "conv":
        # [(periods,)] B, W-1, C
        logical = ("batch", None, "conv_ch")
    elif name == "state":
        # [(periods,)] B, H, N, P
        logical = ("batch", None, None, None)
    else:
        raise KeyError(f"no cache rule for {'/'.join(path)}")
    pad = leaf.ndim - len(logical)
    return spec(*([None] * pad + list(logical)))


def cache_pspecs(caches) -> object:
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: _cache_leaf_spec(_path_names(kp), leaf), caches)
