"""ContinuousEngine: sampling-capable serving with continuous batching,
prefix caching, and chunked prefill.

The engine drives the stack through a generic per-layer **decode-state
protocol** (``models.transformer.init_serving_state``): each layer kind
declares its own decode state and its prefill/decode apply. Attention
mixers declare paged KV pools (``[P, page, Hkv, Dh]``, indexed by the
shared page table); mamba mixers declare a pooled, constant-size per-*slot*
state (conv tail + ``[slot, H, N, P]`` SSD state) — recurrent state folds
all history into fixed size, so it rides the decode slot, not pages. That
one protocol serves dense, MoE, VLM, pure-SSM (mamba2), and hybrid (jamba)
families with the same scheduler: slot recycling resets a mamba row at the
next sequence's first chunk, and preemption stays forced replay — the SSM
state is recomputed by re-prefilling the victim's context, so resume is
token-identical. Prefix caching shares *pages*, which recurrent state is
not decomposable into, so SSM-bearing archs gate it off with an explicit
reason on the engine and in every request's result (never a silent no-op).

Shapes the compiler sees are fixed — decode always runs the full
``num_slots`` batch against the same page pools and a [num_slots, max_pages]
page table — so requests join and leave mid-flight without recompiling.
Prompt ingestion is *chunked prefill*: one page-multiple chunk of one
sequence per engine iteration, written straight into the sequence's pages by
the paged-prefill path (``models.transformer.paged_prefill_stack``), so

- a long prompt no longer stalls every running decode for a full-prompt
  forward pass (decode steps interleave between its chunks), and
- the prefill compile cache holds exactly ONE shape (the chunk), not one
  entry per page-aligned bucket length.

Prefix caching closes the loop: the scheduler's radix index matches each
prompt against already-resident pages (shared via refcounts; a partially
matching tail page is copied on divergence — the engine's CoW device copy),
and only the unmatched suffix is chunk-prefilled. Under shared system
prompts this removes most prefill FLOPs *and* most prefill HBM writes.

Tensor parallelism (``tp > 1``) runs the same engine over a 1-D ``("model",)``
device mesh: the page pools are *head-sharded* (each device owns
``num_kv_heads / tp`` heads of every physical page, so page ids — and
therefore the host-side ``PageAllocator``/``PrefixIndex``/scheduler — stay
global and unchanged), the attention/MLP projections are Megatron shards,
and the decode/prefill/copy steps run under ``shard_map`` with one
all-reduce per psum site (attention output; MLP output or MoE combine).
When ``tp > num_kv_heads``, KV projections and pools are *replicated*
head-major (``kv_rep = tp / Hkv`` shards per KV head) so each shard still
owns one whole head. MoE layers run expert-parallel: routed experts shard
E-major (each device owns ``E / tp`` complete experts, routing replicated)
and the combine meets in the layer's single psum. Mamba mixers stay
replicated — collective-free. Embedding, norms, and the LM head stay
replicated, so every shard computes identical logits and identical sampler
draws — the emitted token vector needs no collective, and greedy/seeded
streams are token-identical across tp values and to the single-device
engine (including preemption replay).

Token selection is the shared on-device sampler (``serving.sampling``):
each request carries ``SamplingParams`` (temperature / top-k / top-p /
seed), and the key for the token at stream position p is
``fold_in(key(seed), p)`` — independent of the slot the request landed in,
of its co-batched neighbours, and of whether the token came from a decode
step or the final chunk of a (re-)prefill. At ``temperature == 0`` the
sampler short-circuits to raw argmax, bit-identical to the historical
greedy engine, and preemption is *forced replay* either way: a victim's
prompt + generated tokens are re-prefilled as forced context, so the resumed
stream is token-identical under any sampling setting (the invariant
``tests/test_sampling.py`` pins, including mid-prefill and CoW-tail
preemptions).

Multi-step compiled decode (``decode_steps = N > 1``) moves N decode
iterations into one on-device ``lax.while_loop`` per host dispatch
(``models.transformer.paged_decode_loop``): the loop carries the sampled
token, per-slot sequence lengths (positions — and therefore PRNG keys —
advance *in-carry*, which is what keeps streams bit-identical to N=1), the
emitted-token buffer, and an exit-reason vector, and exits *globally* the
first iteration any active slot hits EOS, its token budget, or its
pre-allocated page capacity — so every returned token is valid and the
host appends exactly ``k`` tokens per active slot. The host resyncs once
per dispatch: it pre-computes the per-slot predicates (budget left, page
capacity via ``Scheduler.extend_capacity`` — free pages only, never a
preemption), then reconciles the returned ``(buffer, k, reasons)`` through
the ordinary finish/admit/preempt path. Invariants the tests pin:

- jit-cache key: ``("decode", sampled, filtered)`` at N=1 (the
  single-step path is literally unchanged) and
  ``("decode", sampled, filtered, N)`` at N>1 — prefill keys
  (``("prefill", final, sampled, filtered)``) never carry the horizon.
  ``analysis/recompile.py`` audits both shapes closed.
- ``steps`` counts loop iterations, ``decode_dispatches`` host dispatches,
  ``decode_exits`` why each dispatch returned; at N=1 the two counters are
  equal and no exit accounting runs.
- a preemption can only land *between* dispatches; forced replay re-derives
  every key from stream position, so the horizon is token-invisible.

The decode step and the prefill chunk carry the residual stream as an
(x, pending-delta) pair inside each layer period, folded by the fused
residual+norm kernels (``kernels.fused_layernorm.decode_residual_norm``)
and completed by a plain add at the period boundary, so no residual add
makes its own HBM round-trip. The LM head and token selection are one
vocab-tiled streaming epilogue (``kernels.fused_lm_head``) that carries
max/argmax, the top-k/top-p bisection counts, softmax masses, and the
inverse-CDF draw in the GEMM accumulator — no [S, V] logits buffer — on
a backend that has the kernel and where each tp shard's vocab slice is a
whole number of 128-wide reduction tiles (``fused_decode``). Elsewhere
``_head`` materializes the logits and runs ``sample_tokens``: the same
tokens, because every float reduction is the same canonically-tiled sum
in both. Post-norm stacks and MLM-transform heads are refused at
construction.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Any, Deque, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis.sanitize import (check_engine, check_finite_probe,
                                 sanitize_enabled)
from ..core.spans import span
from ..kernels.decode_attention import ops as attn_ops
from ..kernels.fused_lm_head import ops as head_ops
from ..kernels.fused_lm_head import ref as head_ref
from ..models import transformer as tf
from ..models.layers import apply_norm, pad_vocab, unembed
from ..models.model import Model
from ..models.moe import capacity_per_row
from ..parallel import sharding as shardlib
from .kv_cache import pages_needed
from .sampling import sample_tokens
from .scheduler import Request, Scheduler, SequenceState

SERVABLE_FAMILIES = ("dense", "moe", "vlm", "ssm", "hybrid")

TP_AXIS = "model"


def _named(impl, name: str):
    """``impl`` under ``name``: jit calls the program it compiles
    ``jit_<name>``, which is the module's name in HLO and its events' name on
    a profiler trace's ``XLA Modules`` line (a ``functools.partial`` of a
    method would compile as ``jit__unknown``)."""
    impl = functools.partial(impl)
    impl.__name__ = name
    return impl


def _split_fused_qkv(params, arch):
    """Replace every attention block's fused ``wqkv``/``bqkv`` with the
    equivalent ``wq/wk/wv`` (``bq/bk/bv``) column slices.

    Head-sharding needs head-major contiguous weight columns per projection;
    a slice of the *fused* feature dim would mix q and kv columns. The split
    is exact — each output column's GEMM is untouched — so tp > 1 engines
    built from fused-init params emit bit-identical projections. Handles
    both period-dict and scanned (leading period axis) layouts, since the
    split runs on the trailing axis.
    """
    cuts = [arch.q_dim, arch.q_dim + arch.kv_dim]

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, val in tree.items():
            if key == "attn" and isinstance(val, dict) and "wqkv" in val:
                val = dict(val)
                wq, wk, wv = jnp.split(val.pop("wqkv"), cuts, axis=-1)
                val.update(wq=wq, wk=wk, wv=wv)
                if "bqkv" in val:
                    bq, bk, bv = jnp.split(val.pop("bqkv"), cuts, axis=-1)
                    val.update(bq=bq, bk=bk, bv=bv)
            out[key] = walk(val)
        return out
    return walk(params)


def _replicate_kv_heads(params, arch, rep: int):
    """Repeat every K/V projection's head blocks ``rep`` times (head-major:
    new head j holds old head j // rep), so the column-parallel slice of a
    ``tp > Hkv`` mesh lands each shard on one complete KV head.

    The GQA math is untouched: shard i's Hq/tp query heads all group onto
    old KV head ``i // rep``, which is exactly the replicated block the
    shard receives — attention per shard is a smaller-head instance of the
    single-device layer, at rep x the global KV memory (the price of
    replication, reported by ``tp_stats``)."""
    hd = arch.resolved_head_dim

    def rep_heads(w):
        # [..., Hkv * hd] -> [..., Hkv, hd] -> repeat -> [..., Hkv * rep * hd]
        shape = w.shape[:-1] + (w.shape[-1] // hd, hd)
        r = jnp.repeat(w.reshape(shape), rep, axis=-2)
        return r.reshape(w.shape[:-1] + (w.shape[-1] * rep,))

    def walk(tree):
        if not isinstance(tree, dict):
            return tree
        out = {}
        for key, val in tree.items():
            if key == "attn" and isinstance(val, dict) and "wk" in val:
                val = dict(val)
                for name in ("wk", "wv", "bk", "bv"):
                    if name in val:
                        val[name] = rep_heads(val[name])
            out[key] = walk(val)
        return out
    return walk(params)


class ContinuousEngine:
    def __init__(self, model: Model, params, *, num_slots: int = 8,
                 num_pages: int = 256, page_size: int = 16,
                 max_seq_len: int = 512, prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None, tp: int = 1,
                 mesh=None, sanitize: Optional[bool] = None,
                 decode_steps: int = 1):
        arch = model.arch
        assert arch.family in SERVABLE_FAMILIES, \
            (f"continuous engine serves families {SERVABLE_FAMILIES}; "
             f"{arch.name} is {arch.family!r}")
        assert not arch.bidirectional, "encoder-only archs have no decode step"
        # the paged stack defers every residual add into the next pre-norm
        # (tf.paged_decode_period) and the head streams straight off the
        # final norm: neither has a post-norm or an MLM-transform form
        assert not arch.post_norm, \
            f"the paged serving stack is pre-norm only ({arch.name})"
        assert not arch.mlm_transform, \
            f"the serving head has no MLM transform ({arch.name})"
        kinds = tf.layer_kinds(arch)
        self.has_attn = any(m == "attn" for m, _ in kinds)
        self.has_ssm = any(m == "mamba" for m, _ in kinds)
        # MoE stacks' decode steps also return their expert counters
        self.has_moe = any(moe for _, moe in kinds)
        if self.has_attn:
            assert arch.num_heads > 0
            assert arch.pos_emb in ("rope", "mrope", "none"), \
                "paged decode re-derives positions from seq_lens " \
                "(rope/mrope/none only)"
            assert arch.window == 0, \
                "paged decode-attention has no sliding-window masking yet"
        self.model = model
        self.arch = arch
        self.page_size = page_size
        self.num_slots = num_slots
        self.max_pages_per_seq = pages_needed(max_seq_len, page_size)
        if prefill_chunk is None:
            prefill_chunk = 4 * page_size
        assert prefill_chunk % page_size == 0 and prefill_chunk > 0, \
            "prefill chunk must be a positive page multiple"
        self.prefill_chunk = prefill_chunk
        # runtime sanitizer (repro.analysis.sanitize): host invariant sweep
        # after every request completion + NaN/Inf probes compiled into the
        # steps. Static per engine — probe variants live in the jit cache
        # keyed by construction, so toggling means a new engine, not a
        # retrace of this one.
        self.sanitize = sanitize_enabled() if sanitize is None \
            else bool(sanitize)
        # multi-step compiled decode: decode_steps > 1 dispatches up to N
        # iterations as one on-device lax.while_loop (tf.paged_decode_loop)
        # and resyncs with the host only on an exit event (EOS, token/page
        # budget) or the horizon. Static per engine — the horizon is part of
        # the decode variant's jit-cache key, so changing N means a new
        # variant, never a retrace. Token streams are bit-identical across N
        # (positions advance in-carry exactly as the host would have).
        assert decode_steps >= 1, decode_steps
        self.decode_steps = int(decode_steps)
        # the streaming LM head (kernels.fused_lm_head) serves where the
        # backend has it and each tp shard's vocab slice is whole reduction
        # tiles; otherwise `_head` materializes the logits (same tokens)
        self.fused_decode = head_ops.tp_fusable(pad_vocab(arch.vocab_size),
                                                tp)
        self.fused_decode_off_reason: Optional[str] = None if \
            self.fused_decode else (
                f"vocab shard {pad_vocab(arch.vocab_size)}/{tp} does not "
                f"land on the {head_ops.RED_TILE}-wide reduction tile")
        # prefix caching shares *pages*; a mamba mixer's recurrent state is
        # not page-decomposable (a cached KV page is useless without the SSM
        # state at its boundary), so SSM-bearing archs gate it off — loudly:
        # the reason lands on the engine AND in every request's result
        self.prefix_cache_off_reason: Optional[str] = None
        if self.has_ssm and prefix_cache:
            self.prefix_cache_off_reason = (
                "prefix cache unsupported for SSM-bearing archs "
                f"({arch.name}): recurrent state is not page-decomposable")
            prefix_cache = False
        self.scheduler = Scheduler(num_slots=num_slots, num_pages=num_pages,
                                   page_size=page_size,
                                   max_pages_per_seq=self.max_pages_per_seq,
                                   prefix_cache=prefix_cache)
        self.pools = tf.init_serving_state(arch, num_pages, page_size,
                                           num_slots, jnp.dtype(arch.dtype))

        # ---- tensor parallelism over a 1-D ("model",) mesh -------------------
        assert tp >= 1, tp
        self.tp = tp
        self.kv_rep = 1
        # psums per period: one per attention output, one per MLP/MoE tail
        # (mamba mixers are replicated — collective-free)
        self._psums_per_step = sum(
            (1 if mixer == "attn" else 0) + (0 if arch.family == "ssm" else 1)
            for mixer, _ in kinds) * (arch.num_layers // len(kinds))
        if tp > 1:
            if arch.moe is not None:
                assert arch.moe.num_held % tp == 0, \
                    (f"tp={tp} must divide the held expert count "
                     f"({arch.moe.num_held}) — expert-parallel layout")
                if arch.moe.num_shared_experts:
                    shared_ff = (arch.moe.expert_ff or arch.d_ff) \
                        * arch.moe.num_shared_experts
                    assert shared_ff % tp == 0, (shared_ff, tp)
            if self.has_attn:
                assert arch.num_heads % tp == 0, \
                    (f"tp={tp} must divide query heads ({arch.num_heads}) — "
                     "head-sharded layout")
                hkv = arch.num_kv_heads
                assert hkv % tp == 0 or tp % hkv == 0, \
                    (f"tp={tp} must divide the KV heads ({hkv}) or be a "
                     "multiple of them (KV-head replication)")
                if hkv % tp:
                    self.kv_rep = tp // hkv
            if arch.d_ff:
                assert arch.d_ff % tp == 0, (arch.d_ff, tp)
            if mesh is None:
                from ..launch.mesh import make_tp_mesh
                mesh = make_tp_mesh(tp)
            assert mesh.shape[TP_AXIS] == tp, (dict(mesh.shape), tp)
            self.mesh = mesh
            self.tp_axis: Optional[str] = TP_AXIS
            # fused qkv cannot be head-sharded; split (exact) then shard
            params = _split_fused_qkv(params, arch)
            if self.kv_rep > 1:
                # tp > Hkv: replicate each KV head across tp/Hkv shards so
                # the head-major column slice stays one whole head per shard
                params = _replicate_kv_heads(params, arch, self.kv_rep)
                self.pools = jax.tree_util.tree_map_with_path(
                    lambda kp, l: jnp.repeat(l, self.kv_rep, axis=-2)
                    if str(kp[-1].key) in shardlib.PAGED_STATE_LEAVES else l,
                    self.pools)
            self._param_specs = shardlib.serving_param_pspecs(params)
            self._pool_specs = shardlib.paged_pool_pspecs(self.pools)
            params = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._param_specs,
                is_leaf=lambda s: isinstance(s, P)))
            self.pools = jax.device_put(self.pools, jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._pool_specs,
                is_leaf=lambda s: isinstance(s, P)))
        else:
            self.mesh = None
            self.tp_axis = None
            self._param_specs = self._pool_specs = None
        self.params = params
        # tokens one block of the paged decode-attention walk covers, at
        # the shard's local KV heads: the kernel's own rule, for kv_blocks
        self.decode_block_tokens = 0
        if self.has_attn:
            row_bytes = (arch.num_kv_heads * self.kv_rep // tp
                         * arch.resolved_head_dim
                         * jnp.dtype(arch.dtype).itemsize)
            self.decode_block_tokens = page_size * attn_ops.pages_per_block(
                page_size, row_bytes, self.max_pages_per_seq)

        self.steps = 0                  # decode steps executed (for stats)
        self.decode_dispatches = 0      # host round-trips those steps cost
        # why multi-step dispatches came back to the host (per active slot
        # bit for eos/budgets; one count per full-horizon dispatch)
        self.decode_exits = {"eos": 0, "token_budget": 0, "page_budget": 0,
                             "horizon": 0}
        self.prefills = 0               # prefill completions (== admissions)
        self.prefill_tokens = 0         # prompt tokens actually computed
        self.cached_prefill_tokens = 0  # prompt tokens served from the cache
        self.cow_copies = 0             # divergent tail pages duplicated
        self.collective_bytes = 0       # analytic TP wire bytes per device
        self._prefilling: Deque[SequenceState] = deque()
        # donate the page pools through decode AND prefill: without it each
        # call copies every layer's [P, page, Hkv, D] pool to update a few rows
        self._donate_pools = jax.default_backend() in ("tpu", "gpu")
        # one compiled entry per static variant (the flags select which
        # sampler work exists at all); built lazily so e.g. all-greedy
        # traffic never compiles a sampled step
        self._jit_cache: Dict[Tuple, Any] = {}
        # the compiled all-greedy decode variant never reads the sampling
        # arrays; ship these cached placeholders instead of rebuilding and
        # re-transferring [S] arrays every step of the default path
        self._null_sampling = (
            jnp.zeros((num_slots,), jnp.uint32),    # seeds
            jnp.zeros((num_slots,), jnp.float32),   # temperatures
            jnp.zeros((num_slots,), jnp.int32),     # top_k
            jnp.ones((num_slots,), jnp.float32),    # top_p
        )
        # sampled traffic reuses its per-slot sampling arrays too: they only
        # change when a slot is (re)assigned, so the decode loop rebuilds
        # them on composition change instead of paying four host->device
        # transfers per step (positions are derived on device from seq_lens
        # — see _decode_impl). This host tax, not the filter math, was most
        # of the sampled-vs-greedy throughput gap.
        self._sampling_key: Optional[Tuple] = None
        self._sampling_args = self._null_sampling

    # ------------------------------------------------------------ jit builders --
    def _build(self, impl, in_specs, out_specs, donate, key=()):
        """jit (and, at tp > 1, shard_map) one static variant of a step.

        ``key`` is the jit-cache key this compiled step lives under — unused
        here, but the recompilation auditor (``repro.analysis.recompile``)
        overrides this method and needs it to attribute trace signatures."""
        if self.mesh is not None:
            impl = shardlib.shard_map_tp(impl, self.mesh, in_specs, out_specs)
        return jax.jit(impl,
                       donate_argnums=donate if self._donate_pools else ())

    def _decode_fn(self, sampled: bool, filtered: bool):
        key = ("decode", sampled, filtered)
        if key not in self._jit_cache:
            impl = _named(functools.partial(
                self._decode_impl, sampled=sampled, filtered=filtered),
                "serve_decode_step")
            in_specs = (self._param_specs, self._pool_specs, P(None, None),
                        P(None), P(None), P(None), P(None), P(None), P(None))
            out_specs = (P(None), self._pool_specs)
            if self.sanitize:
                out_specs += (P(),)     # the replicated isfinite probe
            self._jit_cache[key] = self._build(
                impl, in_specs, out_specs, donate=(1,), key=key)
        return self._jit_cache[key]

    def _decode_multi_fn(self, sampled: bool, filtered: bool):
        """The multi-step decode variant: same static flags as
        ``_decode_fn`` plus the horizon N, which keys the jit cache — an
        engine at ``decode_steps=N`` compiles (lazily, per sampling
        variant) loops of exactly that horizon and nothing else."""
        key = ("decode", sampled, filtered, self.decode_steps)
        if key not in self._jit_cache:
            impl = _named(functools.partial(
                self._decode_multi_impl, sampled=sampled, filtered=filtered,
                horizon=self.decode_steps), "serve_decode_loop")
            in_specs = (self._param_specs, self._pool_specs, P(None, None)) \
                + (P(None),) * 10
            out_specs = (P(None, None), P(), P(None), self._pool_specs)
            if self.sanitize:
                out_specs += (P(),)     # the replicated isfinite probe
            self._jit_cache[key] = self._build(
                impl, in_specs, out_specs, donate=(1,), key=key)
        return self._jit_cache[key]

    def _prefill_fn(self, final: bool, sampled: bool, filtered: bool):
        key = ("prefill", final, sampled, filtered)
        if key not in self._jit_cache:
            impl = _named(functools.partial(
                self._prefill_impl, final=final, sampled=sampled,
                filtered=filtered), "serve_prefill_chunk")
            in_specs = (self._param_specs, self._pool_specs, P(None, None),
                        P(None), P(), P(), P(), P(), P(), P(), P(), P())
            out_specs = (P(), self._pool_specs)
            if self.sanitize:
                out_specs += (P(),)
            self._jit_cache[key] = self._build(
                impl, in_specs, out_specs, donate=(1,), key=key)
        return self._jit_cache[key]

    def _copy_page_fn(self):
        key = ("copy",)
        if key not in self._jit_cache:
            # pools are argument 0 here, not 1
            self._jit_cache[key] = self._build(
                _named(self._copy_page_impl, "serve_copy_page"),
                (self._pool_specs, P(), P()), self._pool_specs, donate=(0,),
                key=key)
        return self._jit_cache[key]

    def _tp_collective_bytes(self, positions: int) -> int:
        """Analytic per-device wire bytes for one step's collectives: one
        fp32 [positions, d_model] ring all-reduce per psum (attention
        output, MLP output / MoE combine — mamba mixers are replicated and
        contribute none), each moving 2 * (tp-1)/tp of its payload per
        device."""
        if self.tp <= 1:
            return 0
        payload = positions * self.arch.d_model * 4
        return self._psums_per_step * payload * 2 * (self.tp - 1) // self.tp

    # ------------------------------------------------------------- jitted fns ---
    def _head(self, params, x, positions, seeds, temps, top_ks, top_ps, *,
              sampled, filtered):
        """Final norm + LM head + token selection: final hidden ``x``
        [S, 1, D] -> ``(tokens [S], ok [S])``, ``ok`` the per-row
        all-finite probe of the logits.

        Greedy slots take a raw argmax, sampled slots a per-slot (seed,
        position)-keyed categorical draw; ``sampled``/``filtered`` are the
        static variant flags (an all-greedy step compiles to a pure
        argmax, temperature-only steps skip the top-k/top-p filter).

        Where the backend has the streaming kernel and the vocab shard fits
        (``fused_decode``), the unembed GEMM streams over vocab tiles with
        the sampling statistics (max/argmax, filter-threshold bisections,
        softmax masses, the inverse-CDF draw) carried in the accumulator:
        no [S, V] logits buffer. Under TP each shard streams its own vocab
        slice and the combines move O(S * V / RED_TILE) statistics, never
        logits. Elsewhere the logits are materialized and drawn from by
        ``sample_tokens`` — the same tokens, since both take the same
        canonically-tiled reductions (``kernels.fused_lm_head.ref``)."""
        arch = self.arch
        x = shardlib.constrain(x, "batch", None, None)
        hidden = apply_norm(arch.norm, params["final_norm"], x, arch.norm_eps)
        if not (head_ops.supported() and self.fused_decode):
            tied = params["embed"]["embedding"] if arch.tie_embeddings \
                else None
            logits = unembed(params.get("out", {}), hidden, tied,
                             arch.logit_softcap)[:, 0]
            if not sampled:
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                tok = sample_tokens(logits, seeds, positions, temps,
                                    top_ks, top_ps, filtered=filtered)
            return tok, jnp.isfinite(logits).all(axis=-1)
        if arch.tie_embeddings:
            w = params["embed"]["embedding"].astype(hidden.dtype).T
        else:
            w = params["out"]["head"].astype(hidden.dtype)
        w = shardlib.constrain(w, None, "vocab")
        hidden = hidden.reshape(hidden.shape[0], hidden.shape[-1])
        rs = head_ref.row_uniforms(seeds, positions)
        softcap = arch.logit_softcap if arch.logit_softcap > 0 else None
        return head_ops.head_tokens(
            hidden, w, rs, temps, top_ks, top_ps, sampled=sampled,
            filtered=filtered, softcap=softcap, axis_name=self.tp_axis,
            tp=self.tp)

    def _decode_impl(self, params, pools, page_table, seq_lens, tokens,
                     seeds, temps, top_ks, top_ps, *, sampled, filtered):
        """tokens [S] -> (next token [S], new pools). S == num_slots. For a
        MoE stack the token vector carries the step's two expert counters
        after the S tokens (``[S + 2]``: token-to-held-expert assignments
        and held experts with at least one, summed over MoE layers), so
        they reach the host with the tokens.

        Selection stays on device (``_head``), so only the [S] token vector
        ever crosses to the host, never [S, vocab] logits. The stream
        position of the token this step emits is derived ON DEVICE: every
        earlier token of the sequence is cached except the step's input
        token, so position = seq_lens + 1 — slot- and batch-independent
        (the determinism contract), with no per-step position transfer.
        Mid-prefill slots are masked to seq_lens 0 and temperature 0; their
        draws are discarded on the host."""
        x = self.model._embed(params, tokens[:, None])
        x, pools, counts = tf.paged_decode_stack(
            self.arch, params["blocks"], pools, x, page_table, seq_lens,
            tp_axis=self.tp_axis)
        tok, ok = self._head(params, x, seq_lens + 1, seeds, temps, top_ks,
                             top_ps, sampled=sampled, filtered=filtered)
        if self.has_moe:
            tok = jnp.concatenate([tok, counts])
        if self.sanitize:
            # inactive slots read the null page and may legitimately produce
            # junk — probe only rows with at least one real token resident
            return tok, pools, jnp.all(ok | (seq_lens == 0))
        return tok, pools

    def _decode_multi_impl(self, params, pools, page_table, seq_lens, tokens,
                           active, budget, page_limit, eos_ids, seeds, temps,
                           top_ks, top_ps, *, sampled, filtered, horizon):
        """tokens [S] -> (emitted tokens [horizon, S], steps executed,
        exit-reason bits [S], new pools). One ``lax.while_loop`` around the
        exact single-step body (``tf.paged_decode_loop``): up to ``horizon``
        tokens per slot leave the device per host round-trip instead of one.

        ``active``/``budget``/``page_limit``/``eos_ids`` are the host's
        per-slot loop predicates (decode-eligible mask, remaining token
        allowance, allocated-page capacity in tokens, eos id or -1); the
        sampling arrays are the same per-slot params the single-step variant
        takes, with positions advanced in-carry so every draw's (seed,
        position) key — and therefore every token — matches ``decode_steps=1``
        bit-for-bit."""
        def embed(tok):
            return self.model._embed(params, tok)

        def head(x, positions):
            return self._head(params, x, positions, seeds, temps, top_ks,
                              top_ps, sampled=sampled, filtered=filtered)

        return tf.paged_decode_loop(
            self.arch, params["blocks"], pools, tokens, page_table, seq_lens,
            active, budget, page_limit, eos_ids, horizon=horizon, embed=embed,
            head=head, probe=self.sanitize, tp_axis=self.tp_axis)

    def _prefill_impl(self, params, pools, tokens, page_row, slot, start,
                      total, moe_cap, seed, temp, top_k, top_p, *, final,
                      sampled, filtered):
        """One prompt chunk of one sequence. tokens [1, C] (padded past
        ``total - start`` valid tokens) -> (token after the chunk's last
        valid token [scalar], new pools). One compiled shape (variants on
        the static flags only: non-final chunks exist to fill pages and skip
        the LM head entirely; a final chunk slices the sampling position and
        pays the same ``_head`` as the decode step). ``slot`` addresses
        the sequence's per-slot SSM state rows, ``moe_cap`` is the full
        context's MoE capacity (host-computed with the static engine's exact
        math; attention-only / MoE-free stacks ignore them). The emitted
        token's stream position is ``total``, so its sampling key matches
        the decode step that would have produced it in an uninterrupted run
        — the forced-replay invariant."""
        x = self.model._embed(params, tokens)
        x, pools = tf.paged_prefill_stack(
            self.arch, params["blocks"], pools, x, page_row, start, total,
            slot, moe_cap, tp_axis=self.tp_axis)
        if not final:
            if self.sanitize:
                # chunk-boundary probe: activations of the chunk's valid
                # positions (pad rows past ``total - start`` may be junk)
                pos = start + jnp.arange(x.shape[1])
                live = jnp.isfinite(x) | (pos >= total)[None, :, None]
                return jnp.zeros((), jnp.int32), pools, live.all()
            return jnp.zeros((), jnp.int32), pools
        xl = tf.chunk_final_hidden(x, start, total)
        toks, ok = self._head(params, xl, total[None], seed[None], temp[None],
                              top_k[None], top_p[None], sampled=sampled,
                              filtered=filtered)
        if self.sanitize:
            return toks[0], pools, ok[0]
        return toks[0], pools

    def _copy_page_impl(self, pools, src, dst):
        """Copy-on-write: duplicate one physical page across every attention
        layer. Mamba slot-state leaves have no pages — CoW only exists under
        prefix caching, which SSM-bearing archs gate off, but the leaf map
        stays name-aware so the step is well-defined for any stack."""
        def leaf(key_path, pool):
            if str(key_path[-1].key) not in shardlib.PAGED_STATE_LEAVES:
                return pool
            if pool.ndim == 5:          # scanned stack: [nper, P, page, H, D]
                return pool.at[:, dst].set(pool[:, src])
            return pool.at[dst].set(pool[src])
        return jax.tree_util.tree_map_with_path(leaf, pools)

    # --------------------------------------------------------------- prefill ----
    def _start_prefill(self, seq: SequenceState) -> None:
        """Execute the admission's CoW copy (if any) and queue the suffix."""
        with span("engine.admit", uid=seq.request.uid):
            if seq.cow is not None:
                src, dst = seq.cow
                self.pools = self._copy_page_fn()(
                    self.pools, jnp.int32(src), jnp.int32(dst))
                self.scheduler.cow_done(seq)
                self.cow_copies += 1
            self.cached_prefill_tokens += seq.cached_len
            self._prefilling.append(seq)

    def _advance_prefill(self, now) -> None:
        """Run ONE chunk of the oldest pending prefill; on the final chunk,
        emit the sequence's next token (sampled at stream position
        ``prefill_target`` under the request's SamplingParams) and publish
        its pages into the prefix index."""
        sched = self.scheduler
        while self._prefilling:
            seq = self._prefilling[0]
            if sched.running.get(seq.slot) is not seq:
                self._prefilling.popleft()      # preempted while waiting
                continue
            ctx = seq.context
            start = seq.prefilled
            end = min(start + self.prefill_chunk, seq.prefill_target)
            final = end == seq.prefill_target
            with span("engine.prefill", uid=seq.request.uid,
                      tokens=end - start, final=int(final)):
                chunk = np.zeros((1, self.prefill_chunk), np.int32)
                chunk[0, :end - start] = ctx[start:end]
                page_row = jnp.asarray(sched.cache.page_table[seq.slot])
                sp = seq.request.sampling
                # `sampled`/`filtered` only matter on the final chunk; pin
                # them False otherwise so non-final chunks share one variant
                prefill = self._prefill_fn(
                    final, final and not sp.greedy,
                    final and not sp.greedy and sp.filtered)
                # full-context MoE capacity, computed host-side with the
                # exact math the static engine's dispatch uses
                # (capacity_per_row)
                moe_cap = capacity_per_row(seq.prefill_target,
                                           self.arch.moe) \
                    if self.arch.moe is not None else 0
                out = prefill(
                    self.params, self.pools, jnp.asarray(chunk), page_row,
                    jnp.int32(seq.slot), jnp.int32(start), jnp.int32(end),
                    jnp.int32(moe_cap),
                    jnp.uint32(sp.seed), jnp.float32(sp.temperature),
                    jnp.int32(sp.top_k), jnp.float32(sp.top_p))
                if self.sanitize:
                    tok, self.pools, probe = out
                    check_finite_probe(
                        probe, f"prefill chunk [{start}:{end}) of request "
                               f"{seq.request.uid} (final={final})")
                else:
                    tok, self.pools = out
                seq.prefilled = end
                self.prefill_tokens += end - start
                self.collective_bytes += self._tp_collective_bytes(
                    self.prefill_chunk)
                if final:
                    self._prefilling.popleft()
                    self.prefills += 1
                    sched.register_prefix(seq.slot, ctx)
            if final:
                with span("engine.prefill_wait"):
                    # jaxlint: allow[hot-host-sync] the scheduler must see
                    # the chunk's token before it can admit/close the
                    # sequence — one designed sync per prefill chunk, not
                    # per model step
                    token = int(tok)
                seq.generated.append(token)
                seq.token_times.append(now())
            return

    def _prefill_pending(self, slot: int) -> bool:
        seq = self.scheduler.running.get(slot)
        return seq is not None and seq.prefilled < seq.prefill_target

    # ------------------------------------------------------------------- run ----
    def run(self, requests: Sequence[Request], *,
            time_fn=time.perf_counter) -> Dict[int, dict]:
        """Serve a trace to completion. Requests with ``arrival > 0`` are held
        back until the trace clock reaches them. Returns
        uid -> {"tokens", "token_times", "prompt_len"[, "error"]}."""
        sched = self.scheduler
        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.uid)))
        results: Dict[int, dict] = {}
        t0 = time_fn()
        skip = 0.0                      # simulated idle time (frozen time_fn)

        def now() -> float:
            return time_fn() - t0 + skip

        def finish(seq: SequenceState) -> None:
            # context[:-1] is what's actually in the pages (the last generated
            # token's K/V was never written) — publish it before releasing
            sched.register_prefix(seq.slot, seq.context[:-1])
            sched.finish(seq)
            results[seq.request.uid] = {
                "tokens": list(seq.generated),
                "token_times": list(seq.token_times),
                "prompt_len": len(seq.request.prompt),
                # per-request prefix accounting: how many prompt tokens this
                # request got from cached pages — 0 with a reason when the
                # engine gated the cache off (never a silent no-op)
                "cached_prefill_tokens": seq.cached_len,
            }
            if self.prefix_cache_off_reason is not None:
                results[seq.request.uid]["prefix_cache"] = \
                    f"off: {self.prefix_cache_off_reason}"
            if self.sanitize:
                # full host-invariant sweep at every request boundary: a
                # leak/desync raises naming the request that exposed it
                check_engine(self)

        # one `engine.step` span per iteration, holding the phase spans
        # (`engine.admit`, `.prefill`, `.prefill_wait`, `.schedule`,
        # `.decode`, `.decode_wait`, `.emit`), which never overlap
        step = 0
        while pending or sched.has_work:
            with span("engine.step", i=step):
                step += 1
                while pending and pending[0].arrival <= now():
                    sched.submit(pending.popleft())

                # a prefill whose sequence was preempted must not gate
                # admission (or trip the stall check below against an
                # admittable queue)
                while self._prefilling and sched.running.get(
                        self._prefilling[0].slot) is not self._prefilling[0]:
                    self._prefilling.popleft()
                # with the prefix cache on, admit only while no prefill is
                # in flight (one admission per iteration): serializing
                # admission behind the running prefill lets a later request
                # prefix-match the pages the current one is about to
                # register, which same-wave admission would miss. With it
                # off there is nothing to match — admit everything that
                # fits, PR-1 style
                while sched.prefix is None or not self._prefilling:
                    seq = sched.admit_next()
                    if seq is None:
                        break
                    self._start_prefill(seq)
                for req in sched.take_rejected():
                    results[req.uid] = {
                        "tokens": [], "token_times": [],
                        "prompt_len": len(req.prompt),
                        "error": "context exceeds max_seq_len "
                                 f"({self.max_pages_per_seq} pages/seq)",
                    }

                # one prompt chunk per iteration: decode steps interleave
                # between a long prompt's chunks instead of stalling behind
                # it. The token emitted on the final chunk is always a *new*
                # token: the first generation for a fresh request, the
                # continuation for a resumed preemption (whose prompt +
                # generated context is re-prefilled forced — replay never
                # re-decides an already-emitted token).
                self._advance_prefill(now)

                batch = None
                with span("engine.schedule"):
                    for slot in list(sched.running):
                        seq = sched.running[slot]
                        if seq.done and not self._prefill_pending(slot):
                            finish(seq)
                    if sched.running:
                        # may preempt, never the last running sequence;
                        # victims re-enter later
                        sched.ensure_capacity()
                        # decode the slots whose prefill is complete;
                        # mid-prefill slots are masked to the null page so
                        # the fixed-shape step stays hot
                        slots = [s for s in sched.running_slots()
                                 if not self._prefill_pending(s)]
                        if slots:
                            batch = self._decode_inputs(slots)

                if not sched.running:
                    if pending:
                        wait = max(0.0, pending[0].arrival - now())
                        before = now()
                        time.sleep(min(1e-3, wait))
                        if now() <= before:
                            # injected clock that doesn't advance with real
                            # time: fast-forward the trace instead of
                            # spinning forever
                            skip += max(wait, 1e-9)
                        continue
                    if sched.queue:
                        # not necessarily a stall: if the last running
                        # sequence finished THIS iteration (a preemption
                        # replay whose final chunk completed it), admission
                        # ran earlier while still gated behind that prefill
                        # — retry before declaring the pool dead
                        seq = sched.admit_next()
                        if seq is None:
                            raise RuntimeError(
                                "queue stalled: page pool cannot admit any "
                                "request")
                        self._start_prefill(seq)
                        continue
                    break
                if batch is None:
                    continue

                (host_args, kv_tokens, kv_blocks, sampled, filtered,
                 sampling_args) = batch
                horizon = self.decode_steps
                with span("engine.decode", slots=len(slots),
                          kv_tokens=kv_tokens, horizon=horizon):
                    fn = self._decode_fn if horizon == 1 \
                        else self._decode_multi_fn
                    out = fn(sampled, filtered)(
                        self.params, self.pools,
                        *(jnp.asarray(a) for a in host_args), *sampling_args)
                    if self.sanitize:
                        *out, self.pools, probe = out
                    else:
                        *out, self.pools = out
                if horizon == 1:
                    with span("engine.decode_wait", kv_blocks=kv_blocks):
                        if self.sanitize:
                            check_finite_probe(probe,
                                               f"decode step {self.steps}")
                        # jaxlint: allow[hot-host-sync] THE per-step sync:
                        # continuous batching is host-driven — stop checks
                        # and slot reuse need this step's tokens before the
                        # next batch can be scheduled
                        next_np = np.asarray(out[0])
                    # a MoE step's expert counters ride after the tokens
                    moe_args = {} if not self.has_moe else {
                        "expert_rows": int(next_np[self.num_slots]),
                        "experts_touched": int(next_np[self.num_slots + 1])}
                    with span("engine.emit", **moe_args):
                        self.steps += 1
                        self.decode_dispatches += 1
                        self.collective_bytes += \
                            self._tp_collective_bytes(self.num_slots)
                        t_tok = now()
                        for slot in slots:
                            seq = sched.running[slot]
                            sched.cache.seq_lens[slot] += 1  # input cached
                            seq.generated.append(int(next_np[slot]))
                            seq.token_times.append(t_tok)
                            if seq.done:
                                finish(seq)
                    continue

                # multi-step dispatch: up to `horizon` decode iterations ran
                # as one compiled while_loop; the host resyncs once per
                # dispatch and replays the loop's effects (seq_lens advance,
                # emitted tokens, finish events) from the returned exit state
                buf, n_steps, reasons = out
                with span("engine.decode_wait", kv_blocks=kv_blocks):
                    if self.sanitize:
                        check_finite_probe(
                            probe, f"multi-step decode dispatch "
                                   f"{self.decode_dispatches} (horizon "
                                   f"{horizon})")
                    # THE per-horizon sync — the one intentional host
                    # round-trip every `horizon` decode steps: the scheduler
                    # must replay the loop's exit state (steps executed,
                    # tokens emitted, per-slot exit reasons) before it can
                    # admit, preempt, or allocate pages. max(1, ...) is for
                    # the recompile auditor's recorder, which replays
                    # all-zero outputs; the real loop always executes >= 1
                    # iteration because the host guaranteed iteration 0's
                    # predicates (ensure_capacity allocated the next page
                    # and done sequences never reach the dispatch).
                    # jaxlint: allow[hot-host-sync] designed per-horizon sync
                    k = max(1, int(n_steps))
                    # jaxlint: allow[hot-host-sync] same per-horizon sync
                    buf_np = np.asarray(buf)
                    # jaxlint: allow[hot-host-sync] same per-horizon sync
                    reasons_np = np.asarray(reasons)
                with span("engine.emit"):
                    self.steps += k
                    self.decode_dispatches += 1
                    self.collective_bytes += \
                        k * self._tp_collective_bytes(self.num_slots)
                    for name, bit in (("eos", tf.EXIT_EOS),
                                      ("token_budget", tf.EXIT_BUDGET),
                                      ("page_budget", tf.EXIT_PAGES)):
                        self.decode_exits[name] += \
                            int(((reasons_np[slots] & bit) != 0).sum())
                    if k == horizon and not reasons_np[slots].any():
                        self.decode_exits["horizon"] += 1
                    t_tok = now()
                    for slot in slots:
                        seq = sched.running[slot]
                        sched.cache.seq_lens[slot] += k  # k inputs cached
                        seq.generated.extend(int(t) for t in buf_np[:k, slot])
                        seq.token_times.extend([t_tok] * k)
                        if seq.done:
                            finish(seq)
        return results

    def _decode_inputs(self, slots):
        """Host inputs of one decode dispatch over ``slots`` (running, their
        prefill complete) -> ``(host_args, kv_tokens, kv_blocks, sampled,
        filtered, sampling_args)``.

        ``host_args`` are the NumPy arrays the compiled step takes after the
        params and pools: the page table, the sequence lengths (mid-prefill
        slots masked to the null page), each slot's last token and, at
        ``decode_steps > 1``, the loop's per-slot predicates. ``kv_tokens``
        is the tokens the decoded slots hold in the cache (the sum of their
        ``seq_lens``), which the step reads; ``kv_blocks`` the blocks the
        paged decode-attention kernel walks for them in each layer of the
        first step. ``sampling_args`` are device arrays, rebuilt only when
        the decoding composition changes."""
        sched = self.scheduler
        cache = sched.cache
        horizon = self.decode_steps
        loop_args = ()
        if horizon > 1:
            # per-slot loop predicates for the multi-step dispatch,
            # built BEFORE snapshotting the page table: extend_capacity
            # appends best-effort horizon pages the compiled loop must
            # see. budget is the host scheduler's remaining allowance
            # (max-new and page-table capacity), restated as the
            # in-loop EXIT_BUDGET predicate; >= 1 because done
            # sequences were finished above.
            h_active = np.zeros((self.num_slots,), bool)
            h_budget = np.ones((self.num_slots,), np.int32)
            h_pages = np.zeros((self.num_slots,), np.int32)
            h_eos = np.full((self.num_slots,), -1, np.int32)
            for slot in slots:
                seq = sched.running[slot]
                req = seq.request
                h_active[slot] = True
                left = min(
                    req.max_new_tokens - len(seq.generated),
                    seq.max_context - len(req.prompt)
                    - len(seq.generated))
                h_budget[slot] = left
                h_pages[slot] = sched.extend_capacity(
                    slot, min(horizon, left))
                if req.eos_id is not None:
                    h_eos[slot] = req.eos_id
            loop_args = (h_active, h_budget, h_pages, h_eos)
        page_table, seq_lens = cache.page_table, cache.seq_lens
        if len(slots) != len(sched.running):
            page_table = page_table.copy()
            seq_lens = seq_lens.copy()
            for s in sched.running:
                if self._prefill_pending(s):
                    page_table[s] = 0
                    seq_lens[s] = 0
        kv_tokens = int(seq_lens[slots].sum())
        # the step attends over each slot's cache plus the token it writes
        kv_blocks = attn_ops.kv_blocks(seq_lens[slots] + 1,
                                       self.decode_block_tokens) \
            if self.has_attn else 0
        tokens = np.zeros((self.num_slots,), np.int32)
        for slot in slots:
            tokens[slot] = sched.running[slot].generated[-1]
        active = [sched.running[s].request.sampling for s in slots]
        sampled = any(not sp.greedy for sp in active)
        # skip the sampler's [S, V] filter sorts when no co-batched
        # request constrains the distribution (disabled filters are
        # exact no-ops, so variant choice never changes a draw)
        filtered = any(not sp.greedy and sp.filtered for sp in active)
        if sampled:
            # per-slot sampling params are constant while a request
            # occupies its slot; only rebuild + re-transfer the arrays
            # when the decoding composition changes (admission, finish,
            # preemption) — positions come from seq_lens on device
            comp = tuple((s, sched.running[s].request.sampling)
                         for s in slots)
            if comp != self._sampling_key:
                seeds = np.zeros((self.num_slots,), np.uint32)
                temps = np.zeros((self.num_slots,), np.float32)
                top_ks = np.zeros((self.num_slots,), np.int32)
                top_ps = np.ones((self.num_slots,), np.float32)
                for slot in slots:
                    sp = sched.running[slot].request.sampling
                    seeds[slot] = sp.seed
                    temps[slot] = sp.temperature
                    top_ks[slot] = sp.top_k
                    top_ps[slot] = sp.top_p
                self._sampling_args = tuple(jnp.asarray(a) for a in (
                    seeds, temps, top_ks, top_ps))
                self._sampling_key = comp
            sampling_args = self._sampling_args
        else:
            sampling_args = self._null_sampling
        host_args = (page_table, seq_lens, tokens) + loop_args
        return (host_args, kv_tokens, kv_blocks, sampled, filtered,
                sampling_args)

    # ----------------------------------------------------------------- stats ----
    @property
    def live_kv_tokens(self) -> int:
        """Logical tokens resident across running sequences (seq_lens sum)."""
        return self.scheduler.cache.live_tokens

    @property
    def pages_in_use(self) -> int:
        """Distinct physical pages held — with prefix sharing this undercuts
        the logical page count (the dedup the README's memory math prices)."""
        return self.scheduler.allocator.used_count

    def trace_stats(self) -> Dict[str, int]:
        """Jit-cache accounting: ``variants`` is the number of static step
        variants traffic actually exercised, ``traces`` the total XLA traces
        behind them, and ``excess`` their difference — nonzero means some
        variant retraced after its first call (a shape or weak-type leak into
        the traced signature), exactly what the recompilation auditor
        (``repro.analysis.recompile``) and the benchmark gate pin to zero."""
        variants = len(self._jit_cache)
        traces = 0
        for fn in self._jit_cache.values():
            size = getattr(fn, "_cache_size", None)
            traces += int(size()) if size is not None else 1
        return {"variants": variants, "traces": traces,
                "excess": traces - variants}

    def tp_stats(self) -> Dict[str, object]:
        """Tensor-parallel accounting for the benchmark JSON.

        Page ids are global under head sharding, so every device holds (a
        1/tp-heads slice of) every in-use page: per-device *pages* equal the
        global count while per-device *bytes* divide by tp — times ``kv_rep``
        when tp > Hkv forces KV-head replication. Only attention layers hold
        pages; mamba layers instead carry the (replicated) per-slot SSM
        state, reported as ``ssm_state_bytes``. ``collective_bytes`` is the
        analytic per-device ring all-reduce wire traffic of the per-layer
        psums (attention out, MLP out / MoE combine).
        """
        arch = self.arch
        kinds = tf.layer_kinds(arch)
        nper = arch.num_layers // len(kinds)
        n_attn = sum(m == "attn" for m, _ in kinds) * nper
        n_mamba = len(kinds) * nper - n_attn
        page_bytes = (self.page_size * arch.num_kv_heads
                      * arch.resolved_head_dim
                      * 2 * n_attn * jnp.dtype(arch.dtype).itemsize)
        ssm_bytes = 0
        if n_mamba:
            from ..models import ssm as ssm_lib
            s = arch.ssm
            h = ssm_lib.num_ssm_heads(arch)
            ssm_bytes = n_mamba * self.num_slots * (
                h * s.state_dim * s.head_dim * 4          # fp32 SSD state
                + (s.conv_width - 1) * ssm_lib.conv_channels(arch)
                * jnp.dtype(arch.dtype).itemsize)         # conv tail
        return {
            "tp": self.tp,
            "kv_head_replication": self.kv_rep,
            "collective_bytes_per_device": self.collective_bytes,
            "per_device": {
                "pages_in_use": self.pages_in_use,
                "kv_bytes": self.pages_in_use * page_bytes * self.kv_rep
                // self.tp,
                "ssm_state_bytes": ssm_bytes,             # replicated
            },
        }
