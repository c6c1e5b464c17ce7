import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the step (train_step / prefill_step / serve_step per shape kind),
  2. lowers it with ShapeDtypeStruct inputs under the production mesh,
  3. compiles, prints memory_analysis() (fit proof) and cost_analysis(),
  4. parses the compiled HLO for the collective schedule,
  5. derives the three roofline terms (§Roofline) and appends everything to a
     JSON results file consumed by benchmarks/roofline_table.py & EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import (ASSIGNED, SHAPES, RunConfig, cell_supported, get_config,
                       input_specs)
from ..core import characterize, hlotext, roofline
from ..parallel import sharding as sh
from ..train.steps import build_step
from .mesh import make_production_mesh

RESULTS = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# per-arch run overrides needed to fit / run at scale (documented in DESIGN.md).
# microbatch counts are empirical: the analytic heuristic tracks saved residuals,
# but MoE dispatch / logit-CE transients per microbatch dominate for these archs.
ARCH_OVERRIDES = {
    # 400B: fp32 LAMB states exceed a single 256-chip pod no matter the layout;
    # bf16 m/v (beyond-paper, halves Takeaway-8 traffic) + cross-pod ZeRO on the
    # multi-pod mesh make it fit — see EXPERIMENTS.md §Dry-run.
    "llama4-maverick-400b-a17b": {
        "opt_state_dtype": "bfloat16",
        "sharding_overrides": (("opt_flat", ("data", "model")),),
        "train_microbatches": 8,
    },
    "deepseek-moe-16b": {"train_microbatches": 8},
    "jamba-v0.1-52b": {"train_microbatches": 32},
    "mistral-large-123b": {"train_microbatches": 8},
    "command-r-35b": {"train_microbatches": 4},
}


def default_microbatches(arch, shape, n_devices: int = 256,
                         budget_bytes: float = 2.5e9) -> int:
    """Gradient-accumulation heuristic (paper §4.2).

    Saved residuals per device (seq+batch sharded 256-way, bf16, one per block)
    must fit ``budget_bytes``; more microbatches than that only multiplies FSDP
    weight-gather traffic by the accumulation count.
    """
    if shape.kind != "train":
        return 1
    tokens = shape.global_batch * shape.seq_len
    resid = tokens * max(arch.d_model, 1) * 2 * arch.num_layers / n_devices
    mb = max(1, int(-(-resid // budget_bytes)))
    while shape.global_batch % mb:
        mb += 1
    return min(mb, shape.global_batch)


def make_run(arch_name: str, shape_name: str, **overrides) -> RunConfig:
    arch = get_config(arch_name)
    shape = SHAPES[shape_name]
    merged = dict(ARCH_OVERRIDES.get(arch_name, {}))
    merged.update({k: v for k, v in overrides.items() if v is not None})
    train_mb = merged.pop("train_microbatches", None)
    mb = merged.pop("microbatches", None) or \
        (train_mb if shape.kind == "train" and train_mb else None) or \
        default_microbatches(arch, shape)
    shape = dataclasses.replace(shape, microbatches=mb)
    return RunConfig(arch=arch, shape=shape, **merged)


def struct_tree(f, *args):
    return jax.eval_shape(f, *args)


def lower_cell(run: RunConfig, mesh, rules, donate: bool = True):
    """-> (lowered, compiled, specs_used) for one cell on one mesh."""
    bundle = build_step(run)
    batch = input_specs(run.arch, run.shape)
    if run.sharding_overrides:
        rules = dict(rules)
        for name, axis in run.sharding_overrides:
            rules[name] = axis
    with sh.activate(mesh, rules):
        batch_specs = sh.sanitize_tree(bundle.batch_specs_of(batch), batch)
        batch_shardings = {k: NamedSharding(mesh, s)
                           for k, s in batch_specs.items()}
        if run.shape.kind == "train":
            state = struct_tree(bundle.init)
            specs = sh.sanitize_tree(bundle.state_specs(state), state)
            state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                    is_leaf=lambda x: isinstance(x, P))
            fn = jax.jit(bundle.fn,
                         in_shardings=(state_sh, batch_shardings),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state, batch)
        else:
            params, caches = struct_tree(bundle.init)
            pspecs = sh.sanitize_tree(bundle.param_specs_of(params), params)
            cspecs = sh.sanitize_tree(bundle.cache_specs_of(caches), caches)
            p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                                is_leaf=lambda x: isinstance(x, P))
            c_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cspecs,
                                is_leaf=lambda x: isinstance(x, P))
            fn = jax.jit(bundle.fn,
                         in_shardings=(p_sh, c_sh, batch_shardings),
                         out_shardings=(None, c_sh),
                         donate_argnums=(1,) if donate else ())
            lowered = fn.lower(params, caches, batch)
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
    return lowered, compiled, compile_s


def analyze_cell(run: RunConfig, compiled, mesh, compile_s: float) -> dict:
    n_dev = mesh.devices.size
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    text = compiled.as_text()
    # full call-graph cost engine: multiplies while-loop bodies by trip count
    # (XLA's cost_analysis counts scan bodies once — see core/characterize.py)
    cost = characterize.analyze_text(text, n_dev)
    colls = cost.summary()
    terms = roofline.compute_terms(
        flops_per_device=cost.flops, bytes_per_device=cost.bytes,
        colls=colls, n_devices=n_dev, arch=run.arch, shape=run.shape)
    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                       + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        "fits_16gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                      + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        <= 16e9,
    }
    # flash-kernel-adjusted memory term: the Pallas flash kernel (validated in
    # tests/test_kernels + test_attention) keeps score tiles in VMEM; its HBM
    # traffic is q/k/v/o (+grads in bwd) only. The chunked stand-in the dry-run
    # lowers pays the tile traffic at HBM — re-price that bucket analytically.
    flash = None
    arch = run.arch
    if arch.num_heads and cost.by_scope_bytes:
        buckets_b = characterize.bucket_scopes(cost.by_scope_bytes)
        attn_bytes = buckets_b.get("attn_bgemm", 0.0)
        n_attn = sum(1 for i in range(arch.num_layers)
                     if arch.is_attention_layer(i))
        tokens = run.shape.global_batch * run.shape.seq_len \
            if run.shape.kind != "decode" else run.shape.global_batch
        passes = 3 if run.shape.kind == "train" else 1
        io = tokens * (2 * arch.q_dim + 2 * arch.kv_dim) * 2
        if run.shape.kind == "decode":
            # decode reads the whole KV cache once per layer
            io += (run.shape.global_batch * run.shape.seq_len
                   * 2 * arch.kv_dim * 2)
        flash_bytes = passes * n_attn * io / n_dev
        mem_flash_s = max(cost.bytes - attn_bytes + flash_bytes, 0.0) \
            / roofline.V5E.hbm_bw
        flash = {"attn_bucket_bytes": attn_bytes,
                 "flash_bytes": flash_bytes,
                 "memory_s": mem_flash_s}
    return {
        "arch": run.arch.name,
        "shape": run.shape.name,
        "kind": run.shape.kind,
        "microbatches": run.shape.microbatches,
        "mesh": {"shape": dict(mesh.shape), "devices": n_dev},
        "compile_s": round(compile_s, 1),
        "memory": mem,
        "flash_adjusted": flash,
        "cost": {"flops_per_device": cost.flops,
                 "bytes_per_device": cost.bytes,
                 "xla_flops_body_once": float(ca.get("flops", 0.0)),
                 "xla_bytes_body_once": float(ca.get("bytes accessed", 0.0))},
        "collectives": colls.to_dict(),
        "op_taxonomy": hlotext.categorize_ops(text),
        "flops_by_category": dict(cost.by_category),
        "bytes_by_category": dict(cost.by_category_bytes),
        "flops_by_bucket": characterize.bucket_scopes(cost.by_scope),
        "bytes_by_bucket": characterize.bucket_scopes(cost.by_scope_bytes),
        "roofline": terms.to_dict(),
    }


def run_cell(arch_name: str, shape_name: str, multi_pod: bool,
             out_dir: Path = RESULTS, tag: str = "baseline",
             **overrides) -> dict:
    arch = get_config(arch_name)
    shape = SHAPES[shape_name]
    skip = cell_supported(arch, shape)
    mesh_name = "multi" if multi_pod else "single"
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{tag}__{mesh_name}__{arch_name}__{shape_name}.json"
    if skip:
        rec = {"arch": arch_name, "shape": shape_name, "skip": skip,
               "mesh": mesh_name}
        out_path.write_text(json.dumps(rec, indent=1))
        print(f"[dryrun] {arch_name} x {shape_name} ({mesh_name}): {skip}")
        return rec
    run = make_run(arch_name, shape_name, **overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = sh.make_rules(multi_pod=multi_pod)
    print(f"[dryrun] {arch_name} x {shape_name} ({mesh_name}, "
          f"mb={run.shape.microbatches}) lowering...", flush=True)
    lowered, compiled, compile_s = lower_cell(run, mesh, rules)
    rec = analyze_cell(run, compiled, mesh, compile_s)
    rec["tag"] = tag
    out_path.write_text(json.dumps(rec, indent=1))
    m = rec["memory"]
    r = rec["roofline"]
    print(compiled.memory_analysis())
    print(f"[dryrun] {arch_name} x {shape_name}: compile {compile_s:.0f}s | "
          f"peak/dev {m['peak_bytes']/1e9:.2f} GB (fits16: {m['fits_16gb']}) | "
          f"compute {r['compute_s']*1e3:.1f}ms memory {r['memory_s']*1e3:.1f}ms "
          f"collective {r['collective_s']*1e3:.1f}ms -> {r['dominant']} | "
          f"roofline fraction {r['peak_fraction']:.2f}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for a in ASSIGNED:
            for s in SHAPES:
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((args.arch, args.shape))

    failures = []
    for multi in meshes:
        for a, s in cells:
            try:
                run_cell(a, s, multi, Path(args.out), tag=args.tag,
                         microbatches=args.microbatches)
            except Exception as e:  # noqa: BLE001 — report all cell failures
                traceback.print_exc()
                failures.append((a, s, multi, repr(e)))
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("   ", f)
        raise SystemExit(1)
    print("[dryrun] all requested cells compiled OK")


if __name__ == "__main__":
    main()
