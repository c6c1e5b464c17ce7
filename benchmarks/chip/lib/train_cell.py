"""A training cell: the program's jitted train step fed by its synthetic
data pipeline, timed over a window, its first three steps held against the
plain reference.

Set-up builds one object, the compiled step with its state, and drives it
through its first three steps with the window's own call and feed; the
window carries on with that same object. The readings compared:

* ``loss_gap_1`` .. ``loss_gap_3``: each step's loss, relative gap;
* ``grad_norm_gap``: the first gradient's global norm, as the trainer
  reports it (before clipping), relative gap;
* ``grad_gap``: the first gradient as LAMB got it, read from its first
  moment after step 1 (``m = (1 - beta1) g / |g|``), leaf by leaf: the gap
  of each leaf's share of the global norm against the reference's;
* ``grad_dir_gap``: the same first moment's direction, leaf by leaf: the
  distance between the program's and the reference's first gradients,
  each over its own global norm. A norm hardly moves where rows are left
  out (at random weights the gradient is mostly what every row shares);
  the direction moves with the rows' own part;
* ``change_gap``: each leaf's distance from its initial value after three
  steps, read from the fp32 master weights.

Leaf gaps are taken against the larger of the reference leaf's value and
the median leaf's. Leaves whose reference gradient is under a thousandth of
the median leaf's gradient move by round-off alone and are left out. A
cell compares the numbers its traffic file gives a limit.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from . import common, weights
from .common import Check, RunResult, log, span


def _leaf_gap(prog: dict, ref: dict, keep) -> float:
    return _leaf_dist({k: abs(prog[k] - ref[k]) for k in keep}, ref, keep)


def _leaf_dist(dist: dict, ref: dict, keep) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(dist[k] / max(ref[k], med) for k in keep)


def gaps(prog: dict, ref: dict) -> dict:
    """Every number the program's readings can be held to, by name."""
    gnorm = ref["grad_norm"]
    med = float(np.median(list(gnorm.values())))
    keep = [k for k, n in gnorm.items() if n >= 1e-3 * med]
    out = {f"loss_gap_{i}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"]),
                                      start=1)}
    total = math.sqrt(sum(n * n for n in gnorm.values()))
    out["grad_norm_gap"] = abs(prog["grad_norm_total"] - total) / total
    out["grad_gap"] = _leaf_gap(prog["grad_share"], ref["grad_share"], keep)
    out["change_gap"] = _leaf_gap(prog["change"], ref["change"], keep)
    if "dir_dist" in prog:
        out["grad_dir_gap"] = _leaf_dist(prog["dir_dist"], ref["grad_share"],
                                         keep)
    return out


def compare(prog: dict, ref: dict, limits: dict) -> list:
    """The checks: the numbers the cell's limits name, each beside its
    limit."""
    return [Check(name, value, limits[name])
            for name, value in gaps(prog, ref).items() if name in limits]


def flat(tree) -> dict:
    import jax
    return {jax.tree_util.keystr(p): float(v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


class Trainer:
    """The program's train step with its state and feed, as a run drives
    it. Kept apart from the timing so the checks can drive it alone."""

    def __init__(self, cell, seed: int):
        import jax
        import jax.numpy as jnp

        from repro.configs import RunConfig, ShapeConfig
        from repro.data import DataConfig, SyntheticPipeline
        from repro.models import build_model
        from repro.optim import make_optimizer
        from repro.train.steps import build_train_step

        tr = cell.traffic
        self.cell = cell
        self.arch = common.program_arch(cell.config)
        shape = ShapeConfig(cell.name, seq_len=tr["seq_len"],
                            global_batch=tr["batch"], kind="train")
        run = RunConfig(arch=self.arch, shape=shape,
                        optimizer=tr["optimizer"],
                        learning_rate=tr["learning_rate"],
                        weight_decay=tr["weight_decay"],
                        grad_clip=tr["grad_clip"], zero1=False,
                        master_weights=True)
        bundle = build_train_step(run)
        self.step = jax.jit(bundle.fn, donate_argnums=(0,))
        model = build_model(self.arch, fuse_qkv=run.fuse_qkv)
        self.structure = jax.eval_shape(model.init, jax.random.key(0))
        opt = make_optimizer(run)
        structure, dtype = self.structure, jnp.dtype(self.arch.dtype)

        def init(key):
            # the trainer's state layout (train.steps.build_train_step's
            # init) around the benchmark's own float32 weights
            params = weights.generate(structure, key, jnp.float32)
            return {"opt": opt.init(params),
                    "params": jax.tree.map(lambda p: p.astype(dtype),
                                           params)}

        self.key = common.jax_key(seed)
        self.state = jax.jit(init)(self.key)
        self.data = SyntheticPipeline(DataConfig(
            vocab_size=self.arch.vocab_size, seq_len=tr["seq_len"],
            global_batch=tr["batch"], objective=tr["objective"], seed=seed))
        self.feed = self.data.iterator(prefetch=2)

    def next_batch(self):
        import jax
        host = next(self.feed)
        return host, jax.device_put(host)

    def close(self):
        self.feed.close()
        common.free(self.state)
        self.state = None


def program_readings(trainer: Trainer, steps: int = 3):
    """Drive the first ``steps`` steps; -> (readings, host batches). The
    readings keep LAMB's first moment after step 1 on the host
    (``direction``), for the reference to measure once the program's state
    is freed."""
    import jax
    import jax.numpy as jnp

    beta1 = trainer.cell.traffic["beta1"]    # LAMB's first-moment decay
    structure = trainer.structure

    @jax.jit
    def moment_norms(m):
        return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), m)

    @jax.jit
    def change_norms(master, key):
        w0 = weights.generate(structure, key, jnp.float32)
        return jax.tree.map(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))),
                            master, w0)

    losses, batches, share, gnorm = [], [], None, None
    for i in range(steps):
        host, batch = trainer.next_batch()
        batches.append(host)
        with span("step_call"):
            trainer.state, metrics = trainer.step(trainer.state, batch)
        losses.append(metrics["loss"])
        if i == 0:
            gnorm = metrics["grad_norm"]
            share = jax.tree.map(lambda n: n / (1 - beta1),
                                 moment_norms(trainer.state["opt"]["m"]))
            direction = jax.device_get(trainer.state["opt"]["m"])
    change = change_norms(trainer.state["opt"]["master"], trainer.key)
    readings = {"losses": [float(x) for x in losses],
                "grad_norm_total": float(gnorm),
                "grad_share": flat(share), "change": flat(change),
                "direction": direction}
    return readings, batches


def reference_readings(cell, seed: int, batches, direction=None,
                       keep_direction=False, **kw) -> dict:
    """The plain float32 reference over the same weights and batches. With
    ``direction`` (a first gradient, at any scale), ``dir_dist`` is each
    leaf's distance between it and the reference's first gradient, both
    over their global norms; ``keep_direction`` returns the latter, on the
    device, as ``direction``."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    ref = common.reference_module(cell)
    arch = common.program_arch(cell.config)
    structure = jax.eval_shape(build_model(arch).init, jax.random.key(0))
    params = weights.make(structure, common.jax_key(seed), jnp.float32)
    hp = cell.traffic
    dev_batches = [jax.device_put(b) for b in batches]
    if direction is not None:
        direction = jax.device_put(direction)
    with jax.default_matmul_precision("highest"):
        out = ref.train_readings(cell.config, hp, params, dev_batches,
                                 direction=direction,
                                 keep_direction=keep_direction, **kw)
    norms = flat(out["grad_norm"])
    readings = {"losses": out["losses"], "grad_norm": norms,
                "grad_norm_total": math.sqrt(sum(n * n
                                                 for n in norms.values())),
                "grad_share": flat(out["grad_share"]),
                "change": flat(out["change"])}
    if direction is not None:
        readings["dir_dist"] = flat(out["dir_dist"])
    if keep_direction:
        readings["direction"] = out["direction"]
    return readings


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        clock) -> RunResult:
    import jax

    from . import trace as trace_lib
    tr = cell.traffic
    tokens_per_step = tr["batch"] * tr["seq_len"]
    trainer = Trainer(cell, seed)
    prog, batches = program_readings(trainer, tr["check_steps"])
    jax.block_until_ready(trainer.state)
    compiles0 = clock.compiles
    log(f"[setup] compile {clock.seconds:.3f}s over {clock.compiles} "
        f"compiles, persistent-cache hits {clock.hits}")

    trace_dir = common.OUT_DIR / "trace" / cell.name
    traced_steps = 0
    steps = failed = 0
    tracing = False
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
        tracing = True
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    prev = None
    while True:
        with span("batch_fetch"):
            _, batch = trainer.next_batch()
        with span("step_call"):
            trainer.state, metrics = trainer.step(trainer.state, batch)
        steps += 1
        if prev is not None:
            with span("metric_sync"):
                if not math.isfinite(float(prev)):
                    failed += 1
        prev = metrics["loss"]
        now = time.perf_counter() - t0
        if tracing and now >= min(seconds, tr["trace_seconds"]):
            with span("metric_sync"):
                jax.block_until_ready(trainer.state)
            jax.profiler.stop_trace()
            tracing, traced_steps = False, steps
        if now >= seconds:
            break
    with span("metric_sync"):
        jax.block_until_ready(trainer.state)
        if not math.isfinite(float(prev)):
            failed += 1
    window = time.perf_counter() - t0
    in_window = clock.compiles - compiles0
    log(f"[window] {steps} steps of {tokens_per_step} tokens in "
        f"{window:.6f}s; compiles inside the window: {in_window}")

    peak = common.memory_peak_bytes()
    hlo_text = None
    if trace:
        hlo_text = trainer.step.lower(
            trainer.state, batch).compile().as_text()
    trainer.close()
    del batch, metrics
    gc.collect()

    ref = reference_readings(cell, seed, batches,
                             direction=prog.pop("direction"))
    prog["dir_dist"] = ref["dir_dist"]
    checks = compare(prog, ref, tr["limits"])
    log(f"[check] losses program {prog['losses']} reference "
        f"{ref['losses']}")
    result = RunResult(
        end_to_end={"train_tokens_per_s": steps * tokens_per_step / window,
                    "setup_s": setup_s},
        checks=checks, attempted=steps, failed=failed,
        notes=[f"compiles inside the window: {in_window}"])
    result.layer_ctx = {"memory_peak_bytes": peak, "kind": "train",
                        "tokens_per_step": tokens_per_step,
                        "seq_len": tr["seq_len"], "config": cell.config}
    if trace:
        reduced = trace_lib.reduce(trace_lib.load(
            trace_lib.find_xplane(trace_dir)))
        result.layer_ctx.update(reduced=reduced, hlo_text=hlo_text,
                                traced_steps=traced_steps)
        result.breakdown = reduced.breakdown()
        result.busy_s, result.window_s = reduced.busy_s, reduced.window_s
    return result
