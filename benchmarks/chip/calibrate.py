#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from, on the chip, at a
cell's own size: for each seed, the number the run compares, for the
program, for its control and for each fault the cell can have.

    python3 benchmarks/chip/calibrate.py --workload bert-large.phase1-s128 \
        --seeds 11,12,13

Training: the program's first three steps; the control is the plain
reference put in the program's place with every matrix product in float8
(the step below the bfloat16 the configuration states); the fault is the
reference with the loss taken over half of the batch. A step that returns
its state unchanged reads 1 on ``grad_gap``, ``grad_dir_gap`` and
``change_gap`` by their definition and needs no run.

Serving: a window of ``--seconds`` at the cell's own load; the control reads,
at each served position, the gap of the token the float8 reference puts
first; the fault alters every served token where it was produced (its id
plus one).

One JSON line per seed on standard output. The benchmark's runs never run
this.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib import common  # noqa: E402
from lib.common import log  # noqa: E402


def train_seed(cell, seed):
    from lib import train_cell
    from lib.precision import mm_fp8
    trainer = train_cell.Trainer(cell, seed)
    prog, batches = train_cell.program_readings(trainer,
                                                cell.traffic["check_steps"])
    trainer.close()
    del trainer
    gc.collect()
    ref = train_cell.reference_readings(
        cell, seed, batches, direction=prog.pop("direction"),
        keep_direction=True)
    prog["dir_dist"] = ref.pop("dir_dist")
    direction = ref.pop("direction")
    out = {"seed": seed}
    for name, readings in (
            ("program", lambda: prog),
            ("control_fp8", lambda: train_cell.reference_readings(
                cell, seed, batches, direction=direction, mm=mm_fp8)),
            ("half_batch", lambda: train_cell.reference_readings(
                cell, seed, batches, direction=direction,
                half_batch=True))):
        out[name] = train_cell.gaps(readings(), ref)
        gc.collect()
    del direction
    out["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
    out["grad_norm"] = {"program": prog["grad_norm_total"],
                        "reference": ref["grad_norm_total"]}
    return out


def serve_seed(cell, seed, seconds):
    import jax

    from lib import serve_cell, traffic as traffic_lib
    from lib.precision import mm_fp8
    tr = cell.traffic
    engine, arch = serve_cell.build_engine(cell, seed)
    serve_cell.warm_up(engine, arch.vocab_size, seed)
    requests = traffic_lib.generate(tr, seed, arch.vocab_size)
    records, *_ = serve_cell.serve_window(engine, cell, requests, seconds)
    common.free((engine.params, engine.pools))
    del engine
    gc.collect()
    sample = serve_cell.pick_sample(records, seed, tr["check"])
    ref_mod = common.reference_module(cell)
    params = serve_cell.reference_params(cell, seed)
    vocab = cell.config["padded_vocab_size"]
    out = {"seed": seed, "requests": len(sample),
           "tokens": sum(len(r.tokens) for r in sample)}
    with jax.default_matmul_precision("highest"):
        prog = serve_cell.served_gaps(ref_mod, cell.config, params, sample)
        control = serve_cell.served_gaps(ref_mod, cell.config, params,
                                         sample, mm=mm_fp8)
        for rec in sample:
            rec.tokens = [(t + 1) % vocab for t in rec.tokens]
        altered = serve_cell.served_gaps(ref_mod, cell.config, params,
                                         sample)
    for name, gaps in (("program", prog), ("control_fp8", control),
                       ("token_altered", altered)):
        out[name] = {"logit_gap": max(float(g.max()) for g in gaps)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=20.0,
                    help="serving cells: the window before the check")
    args = ap.parse_args(argv)
    src = common.ROOT / "src"
    sys.path.insert(0, str(src))
    cell = common.find_cell(args.workload)
    common.setup_compile_cache()
    try:
        device = common.device_info(cell.chips)
    except common.NoChip as e:
        log(f"no result: {e}")
        return 2
    common.set_log_prefix(device)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = train_seed(cell, seed) if cell.traffic["kind"] == "train" \
            else serve_seed(cell, seed, args.seconds)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
