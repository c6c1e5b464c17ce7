#!/usr/bin/env python3
"""Benchmark regression gate for the serving benchmark JSON artifact.

Compares a fresh ``benchmarks.serving --json`` result against a committed
baseline and fails (exit 1) on a regression beyond tolerance in any gated
metric:

- ``rates.<rate>.continuous.tok_s``      (throughput: lower is a regression)
- ``shared_prefix.{off,on}.tok_s``
- ``shared_prefix.{off,on}.ttft_ms``     (mean TTFT: higher is a regression)
- ``sampled.{greedy,sampled}.tok_s``
- ``sampled.sampler_overhead_pct``       (sampler tax over greedy, in
                                          percentage points: current may
                                          exceed baseline by at most
                                          100 * tolerance points — a
                                          relative gate on a near-zero
                                          percentage would flap on noise)
- ``families.<arch>.tok_s``              (hybrid/SSM/MoE serving sweep)
- ``multistep.n<N>.tok_s``               (multi-step compiled decode loop at
                                          decode_steps N in {1,4,16})
- ``multistep.n<N>.dispatches_per_token`` (host dispatches per decode token:
                                          higher is a regression; the bench
                                          itself also hard-bounds it at
                                          1.1/N)
- ``multistep.n<N>.speedup_vs_n1``       (N>1 throughput over the N=1 run in
                                          the SAME artifact: gated so the
                                          loop never ships slower than
                                          single-step)
- ``multistep.diverged_streams``         (N>1 vs N=1 token mismatches: must
                                          be exactly 0 — determinism bug,
                                          not a perf number)
- ``recompiles.excess``                  (jit cache misses after warmup:
                                          must be exactly 0 — a retrace is
                                          a correctness bug, not a perf
                                          number, so tolerance never applies)

Every metric present in the *baseline* must exist in the current result —
a silently missing section (a partial artifact) fails the gate too. Extra
sections in the current result (e.g. ``tensor_parallel``) are ignored, so
the baseline does not usually need regenerating when new sections land —
EXCEPT the sections in ``REQUIRED_SECTIONS``, which the baseline itself
must carry: a baseline that predates them silently un-gates that coverage,
so the gate fails until it is regenerated.

Usage:
    python tools/check_bench.py serving_bench.json \
        benchmarks/baselines/serving.json [--tolerance 0.2]

Re-baselining (numbers are machine-class specific — regenerate on the CI
runner class, not a laptop): download ``serving_bench.json`` from a green CI
run's artifacts and commit it as ``benchmarks/baselines/serving.json``, or
locally:

    PYTHONPATH=src python -m benchmarks.serving --requests 8 \
        --json benchmarks/baselines/serving.json

The tolerance is deliberately loose (default 20%, override with
``--tolerance`` or the ``CHECK_BENCH_TOLERANCE`` env var): the gate exists
to catch order-of-magnitude perf cliffs (a decode path falling off its
compiled fast path, prefix caching silently disabled), not scheduler noise.
No external dependencies — stdlib only, importable for unit tests.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, Iterator, List, Optional, Tuple

# (metric path, value, direction); direction "higher" = bigger is better,
# "lower" = smaller is better, "zero" = must be exactly 0 (no tolerance),
# "lower_points" = a percentage gated in absolute points
# (cur <= base + 100 * tolerance)
Metric = Tuple[str, float, str]

# sections the BASELINE must carry: absence means it predates the coverage
# (and would silently un-gate it) — regenerate and commit a fresh artifact
REQUIRED_SECTIONS = ("families", "recompiles", "sampled", "multistep")


def iter_metrics(baseline: dict) -> Iterator[Metric]:
    """Yield every gated metric the baseline carries."""
    for rate, d in baseline.get("rates", {}).items():
        if "continuous" in d:
            yield (f"rates.{rate}.continuous.tok_s",
                   d["continuous"]["tok_s"], "higher")
    for tag in ("off", "on"):
        d = baseline.get("shared_prefix", {}).get(tag)
        if d:
            yield f"shared_prefix.{tag}.tok_s", d["tok_s"], "higher"
            yield f"shared_prefix.{tag}.ttft_ms", d["ttft_ms"], "lower"
    for tag in ("greedy", "sampled"):
        d = baseline.get("sampled", {}).get(tag)
        if d:
            yield f"sampled.{tag}.tok_s", d["tok_s"], "higher"
    sampled = baseline.get("sampled", {})
    if "sampler_overhead_pct" in sampled:
        yield ("sampled.sampler_overhead_pct",
               sampled["sampler_overhead_pct"], "lower_points")
    for arch, d in baseline.get("families", {}).items():
        if "tok_s" in d:
            yield f"families.{arch}.tok_s", d["tok_s"], "higher"
    multistep = baseline.get("multistep", {})
    for tag in ("n1", "n4", "n16"):
        d = multistep.get(tag)
        if d:
            yield f"multistep.{tag}.tok_s", d["tok_s"], "higher"
            if "dispatches_per_token" in d:
                yield (f"multistep.{tag}.dispatches_per_token",
                       d["dispatches_per_token"], "lower")
            if "speedup_vs_n1" in d:
                yield (f"multistep.{tag}.speedup_vs_n1",
                       d["speedup_vs_n1"], "higher")
    if "diverged_streams" in multistep:
        yield ("multistep.diverged_streams",
               multistep["diverged_streams"], "zero")
    if "recompiles" in baseline:
        yield ("recompiles.excess",
               baseline["recompiles"].get("excess", 0), "zero")


def lookup(result: dict, path: str) -> Optional[float]:
    """Resolve a dotted metric path. Keys may themselves contain dots (arch
    names like ``mamba2-1.3b``), so at each level the longest join of
    remaining segments that is an actual key wins."""
    node = result
    parts = path.split(".")
    i = 0
    while i < len(parts):
        if not isinstance(node, dict):
            return None
        for j in range(len(parts), i, -1):
            key = ".".join(parts[i:j])
            if key in node:
                node = node[key]
                i = j
                break
        else:
            return None
    return float(node) if isinstance(node, (int, float)) else None


def compare(current: dict, baseline: dict,
            tolerance: float) -> List[Dict[str, object]]:
    """-> one row per gated metric: {metric, baseline, current, ok, note}."""
    rows: List[Dict[str, object]] = []
    for sec in REQUIRED_SECTIONS:
        if baseline and sec not in baseline:
            rows.append({"metric": f"{sec}.<section>", "baseline": None,
                         "current": None, "ok": False,
                         "note": "REQUIRED section absent from baseline — "
                                 "re-baseline (see docstring)"})
    for path, base, direction in iter_metrics(baseline):
        cur = lookup(current, path)
        if cur is None:
            rows.append({"metric": path, "baseline": base, "current": None,
                         "ok": False, "note": "MISSING from current result"})
            continue
        if direction == "zero":
            ok = cur == 0
            note = "zero, as required" if ok else \
                f"{cur:g} != 0 — a correctness invariant broke, not a " \
                "perf number"
        elif direction == "lower_points":
            # percentage metric, gated in absolute points: a relative bound
            # on a near-zero base would reject harmless noise
            ok = cur <= base + 100.0 * tolerance
            note = f"{cur - base:+.1f}pp"
        elif direction == "higher":
            ok = cur >= base * (1.0 - tolerance)
            note = f"{(cur - base) / base:+.1%}" if base else "+0.0%"
        else:
            ok = cur <= base * (1.0 + tolerance)
            note = f"{(cur - base) / base:+.1%}" if base else "+0.0%"
        rows.append({"metric": path, "baseline": base, "current": cur,
                     "ok": ok, "note": note})
    if not rows:
        rows.append({"metric": "<none>", "baseline": None, "current": None,
                     "ok": False, "note": "baseline carries no gated metrics"})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("current", help="fresh benchmarks.serving --json output")
    ap.add_argument("baseline", help="committed baseline JSON")
    ap.add_argument("--tolerance", type=float,
                    default=float(os.environ.get("CHECK_BENCH_TOLERANCE",
                                                 0.2)),
                    help="allowed fractional regression (default 0.2)")
    args = ap.parse_args(argv)
    with open(args.current) as f:
        current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    rows = compare(current, baseline, args.tolerance)
    width = max(len(r["metric"]) for r in rows)
    failed = [r for r in rows if not r["ok"]]
    for r in rows:
        status = "ok  " if r["ok"] else "FAIL"
        base = "-" if r["baseline"] is None else f"{r['baseline']:.2f}"
        cur = "-" if r["current"] is None else f"{r['current']:.2f}"
        print(f"[check_bench] {status} {r['metric']:<{width}} "
              f"base={base} cur={cur} ({r['note']})")
    if failed:
        print(f"[check_bench] {len(failed)}/{len(rows)} metrics regressed "
              f"beyond {args.tolerance:.0%} — see docstring for how to "
              "re-baseline after an intentional change", file=sys.stderr)
        return 1
    print(f"[check_bench] all {len(rows)} metrics within "
          f"{args.tolerance:.0%} of baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
