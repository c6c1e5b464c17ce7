"""train_step / prefill_step / serve_step builders with full sharding metadata.

``build_train_step`` returns (step_fn, state_init_fn, shardings) so both the real
trainer (launch/train.py) and the dry-run (launch/dryrun.py) consume the same code:
the dry-run lowers ``step_fn`` with ShapeDtypeStructs, the trainer jits it with
donated state.

Mixed precision (paper §3.2.1): master params fp32; compute casts to ``arch.dtype``
(bf16 — the TPU adaptation of the paper's fp16+master-copy scheme, no loss scaling
needed); LAMB runs in fp32 exactly as the paper's "updates remain FP32" observation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ArchConfig, RunConfig, ShapeConfig
from ..models import build_model
from ..optim import grad as grad_lib
from ..optim import make_optimizer
from ..parallel import sharding as sh

PyTree = Any


@dataclasses.dataclass
class StepBundle:
    """Everything needed to run or lower one step kind."""
    fn: Callable                      # (state, batch) -> (state, metrics) | serve sig
    init: Callable                    # () -> state (on-device, sharded)
    state_specs: PyTree               # PartitionSpec pytree for state
    batch_specs: Dict[str, P]         # PartitionSpec per batch input
    donate: Tuple[int, ...] = (0,)


# ----------------------------------------------------------------------- train ----

def build_train_step(run: RunConfig) -> StepBundle:
    arch, shape = run.arch, run.shape
    model = build_model(arch, fuse_qkv=run.fuse_qkv)
    opt = make_optimizer(run)

    def loss_fn(params, batch):
        return model.loss(params, batch)

    def step(state: PyTree, batch: Dict[str, jax.Array]):
        params = state["params"]
        transform = None
        if run.zero1 and run.optimizer in ("lamb", "adamw"):
            # accumulate grads directly in the ZeRO flat/sharded layout:
            # the fp32 carry is 1/(D*M) per device (ZeRO-2-style)
            from ..optim import lamb as lamb_lib
            from ..optim import zero as zero_lib
            la = lamb_lib._layer_axes(params) if run.optimizer == "lamb" \
                else jax.tree.map(lambda _: 0, params)

            def transform(g):  # noqa: F811
                flat = jax.tree.map(
                    lambda x, z: zero_lib.flatten_leaf(x, z, 256), g, la)
                return sh.constrain_flat(flat)

        grads, metrics = grad_lib.accumulate_microbatches(
            loss_fn, params, batch, shape.microbatches, transform=transform)
        if run.grad_clip > 0:
            grads, gnorm = grad_lib.clip_by_global_norm(grads, run.grad_clip)
            metrics = dict(metrics, grad_norm=gnorm)
        new_params, new_opt = opt.update(grads, state["opt"], params)
        return {"params": new_params, "opt": new_opt}, metrics

    def init(seed: int = 0):
        params = model.init(jax.random.key(seed))
        if run.master_weights:
            # bf16 params in the model; the optimizer holds the fp32 master copy
            # (paper §3.2.1 mixed precision) — this also halves FSDP traffic.
            state = {"opt": opt.init(params)}
            state["params"] = jax.tree.map(
                lambda p: p.astype(jnp.dtype(arch.dtype)), params)
            return state
        return {"params": params, "opt": opt.init(params)}

    def state_specs_of(state):
        pspecs = sh.param_pspecs(state["params"])
        return {"params": pspecs,
                "opt": sh.opt_state_pspecs(state["opt"], pspecs, run.zero1)}

    bundle = StepBundle(fn=step, init=init, state_specs=state_specs_of,
                        batch_specs=None)
    bundle.batch_specs_of = sh.batch_pspecs
    return bundle


# ----------------------------------------------------------------------- serve ----

def serve_params(model, arch: ArchConfig, seed: int) -> PyTree:
    """Serving uses inference-dtype (bf16) checkpoints, built inside one
    jit so the float32 init values never sit in memory beside the cast copy
    (for a 3B model that pair alone would overflow a 16 GB chip)."""
    dt = jnp.dtype(arch.dtype)

    def init(key):
        return jax.tree.map(lambda p: p.astype(dt), model.init(key))

    return jax.jit(init)(jax.random.key(seed))


def build_prefill_step(run: RunConfig) -> StepBundle:
    arch, shape = run.arch, run.shape
    model = build_model(arch, fuse_qkv=run.fuse_qkv)

    def step(params: PyTree, caches: PyTree, batch: Dict[str, jax.Array]):
        return model.prefill(params, caches, batch)

    def init(seed: int = 0):
        params = serve_params(model, arch, seed)
        caches = model.init_caches(None, shape.global_batch, shape.seq_len)
        return params, caches

    bundle = StepBundle(fn=step, init=init, state_specs=None, batch_specs=None,
                        donate=(1,))
    bundle.param_specs_of = sh.param_pspecs
    bundle.cache_specs_of = sh.cache_pspecs
    bundle.batch_specs_of = sh.batch_pspecs
    return bundle


def build_serve_step(run: RunConfig) -> StepBundle:
    """decode_* cells: one new token against a seq_len KV cache."""
    arch, shape = run.arch, run.shape
    model = build_model(arch, fuse_qkv=run.fuse_qkv)

    def step(params: PyTree, caches: PyTree, batch: Dict[str, jax.Array]):
        return model.decode_step(params, caches, batch)

    def init(seed: int = 0):
        params = serve_params(model, arch, seed)
        caches = model.init_caches(None, shape.global_batch, shape.seq_len)
        return params, caches

    bundle = StepBundle(fn=step, init=init, state_specs=None, batch_specs=None,
                        donate=(1,))
    bundle.param_specs_of = sh.param_pspecs
    bundle.cache_specs_of = sh.cache_pspecs
    bundle.batch_specs_of = sh.batch_pspecs
    return bundle


def build_step(run: RunConfig) -> StepBundle:
    kind = run.shape.kind
    if kind == "train":
        return build_train_step(run)
    if kind == "prefill":
        return build_prefill_step(run)
    if kind == "decode":
        return build_serve_step(run)
    raise ValueError(kind)
