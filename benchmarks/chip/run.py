#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 benchmarks/chip/run.py --workload bert-large.phase1-s128 \
        --seed 7 --seconds 10 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its files are
``configs/<config>.json`` (sizes, with the plain reference ``<config>.py``
beside it) and ``traffic/<traffic>.json`` (the job or the request mix, with
the limits of the correctness checks). ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` takes a profiler trace of the window's
start and reports the per-layer metrics, each read by ``metrics/<name>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: every number compared, beside its limit. The
checks are also the last lines of standard error. Without a TPU, with fewer
chips than the cell asks for, or without the program beside the benchmark,
the run exits nonzero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib import common  # noqa: E402
from lib.common import log  # noqa: E402


def result_line(cell_name, result, device, trace: bool, bench) -> dict:
    if trace:
        ctx = dict(result.layer_ctx, device=device,
                   peaks=common.peaks(device["kind"]))
        metrics = {}
        for m in common.metrics_for(cell_name, "per_layer", bench):
            value = common.load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=result.busy_s,
                      window_s=result.window_s)
    else:
        metrics = {m["name"]: {"value": result.end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in common.metrics_for(cell_name, "end_to_end",
                                               bench)}
    line = {"correct": all(c.ok for c in result.checks),
            "attempted": result.attempted, "failed": result.failed,
            "metrics": metrics, "device": device}
    if trace and result.breakdown is not None:
        line["breakdown"] = result.breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in result.checks}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    src = common.ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"no program to run: {src / 'repro'} is missing")
        return 2
    bench = common.benchmark()
    cell = common.find_cell(args.workload, bench)
    sys.path.insert(0, str(src))
    common.setup_compile_cache()
    try:
        device = common.device_info(cell.chips)
    except common.NoChip as e:
        log(f"no result: {e}")
        return 2
    common.set_log_prefix(device)
    clock = common.CompileClock()
    log(f"[run] {args.workload} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")
    if cell.traffic["kind"] == "train":
        from lib import train_cell as runner
    else:
        from lib import serve_cell as runner
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        T_START, clock)
    device["memory_peak_bytes"] = result.layer_ctx["memory_peak_bytes"]
    line = result_line(args.workload, result, device, bool(args.trace),
                       bench)
    for note in result.notes:
        log(f"[note] {note}")
    for c in result.checks:
        log(f"[check] {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
