"""End-to-end trainer: ``python -m repro.launch.train --arch <id> [...]``.

Runs real optimization (synthetic data) on whatever devices exist — one CPU for
the examples/tests, a real mesh in production. Auto-resumes from the newest
checkpoint, demonstrating the crash/restart contract (tests kill/restart this
under the fault-tolerance suite).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from ..configs import RunConfig, ShapeConfig, get_config, smoke_config
from ..data import DataConfig, SyntheticPipeline
from ..checkpoint import CheckpointManager
from ..train.loop import LoopConfig, train_loop
from ..train.steps import build_train_step
from .compile_cache import enable_compile_cache


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bert-large")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="lamb")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--no-master-weights", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    arch = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train", microbatches=args.microbatches)
    run = RunConfig(arch=arch, shape=shape, optimizer=args.optimizer,
                    learning_rate=args.lr, zero1=False,
                    master_weights=not args.no_master_weights,
                    seed=args.seed)
    bundle = build_train_step(run)
    step_fn = jax.jit(bundle.fn, donate_argnums=(0,))

    objective = "mlm" if arch.bidirectional else "causal"
    data = SyntheticPipeline(DataConfig(
        vocab_size=arch.vocab_size, seq_len=args.seq,
        global_batch=args.batch, objective=objective, seed=args.seed))

    start_step = 0
    state = None
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and ckpt.latest_step() is not None:
        restored = ckpt.restore()
        state = jax.tree.map(jax.numpy.asarray, restored["state"])
        start_step = restored["extra"].get("data_step", restored["step"])
        print(f"[train] resumed from step {start_step}")
    if state is None:
        state = bundle.init(args.seed)

    loop_cfg = LoopConfig(max_steps=args.steps, ckpt_every=args.ckpt_every,
                          log_every=max(args.steps // 20, 1))
    out = train_loop(step_fn, state, data, loop_cfg,
                     start_step=start_step, ckpt=ckpt)
    losses = [h["loss"] for h in out["history"]]
    if losses:
        print(f"[train] loss {losses[0]:.4f} -> {losses[-1]:.4f} "
              f"over {len(losses)} steps "
              f"(stragglers: {out['monitor'].stragglers})")
    return out


if __name__ == "__main__":
    main()
