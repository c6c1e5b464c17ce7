#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: the highest Poisson
arrival rate at which the backlog does not grow.

    python3 benchmarks/chip/knee_sweep.py --config internlm2-1.8b \
        --traffic chat --rates 0.25,0.5,0.75,1.0,1.5 --seconds 60

A fresh engine, warmed, serves the cell's traffic at each rate for
``--seconds`` of arrivals. The backlog is the number of requests that have
arrived and have no first token yet, sampled at every engine clock reading.
For each rate one JSON line gives the tokens per second completed, the
time-to-first-token and inter-token tails, and the backlog in the first and
last quarter of the arrivals. The rate written into the cell's traffic file
is four fifths of the highest rate whose backlog did not grow.
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


class BacklogClock:
    """The engine's ``time_fn``: samples the backlog, and ends the run
    (``Done``) once the arrivals are over and drained, or
    ``limit`` seconds after the start."""

    class Done(Exception):
        pass

    def __init__(self, engine, requests, seconds, limit):
        self.engine, self.seconds, self.limit = engine, seconds, limit
        self.arrivals = sorted((r.arrival, r.uid) for r in requests
                               if r.arrival <= seconds)
        self.first = set()
        self.samples = []
        self.t0 = None

    def __call__(self):
        t = time.perf_counter()
        if self.t0 is None:
            self.t0 = t
        el = t - self.t0
        for s in self.engine.scheduler.running.values():
            if s.generated:
                self.first.add(s.request.uid)
        arrived = sum(1 for a, _ in self.arrivals if a <= el)
        waiting = sum(1 for a, uid in self.arrivals
                      if a <= el and uid not in self.first)
        self.samples.append((el, arrived, waiting))
        if el >= self.limit or (el >= self.seconds and waiting == 0):
            raise BacklogClock.Done
        return t


def sweep_rate(cell, rate, seconds, seed):
    """One rate on a fresh, warmed engine."""
    from lib import serve_cell, traffic as traffic_lib
    engine, arch = serve_cell.build_engine(cell, seed)
    serve_cell.warm_up(engine, arch.vocab_size, seed)
    vocab = arch.vocab_size
    tr = dict(cell.traffic, rate=rate,
              requests=int(rate * seconds * 1.5) + 8)
    requests = traffic_lib.generate(tr, seed, vocab)
    finished = []
    sched = engine.scheduler
    finish = sched.finish

    def record(seq):
        finished.append(seq)
        finish(seq)

    sched.finish = record
    clock = BacklogClock(engine, requests, seconds, seconds * 3)
    try:
        engine.run(serve_cell.to_requests(requests, tr["sampling"]),
                   time_fn=clock)
    except BacklogClock.Done:
        pass
    finally:
        sched.finish = finish
    records = {r.uid: serve_cell.Record(r) for r in requests}
    for seq in finished + list(sched.running.values()):
        records[seq.request.uid].tokens = list(seq.generated)
        records[seq.request.uid].times = list(seq.token_times)
    m = serve_cell.window_metrics(records, seconds)
    q = [w for el, _, w in clock.samples if el <= seconds]
    quarter = max(1, len(q) // 4)
    return {"rate": rate, "requests_due": len(m["ttfts"]),
            "output_tokens_per_s": m["output_tokens_per_s"],
            "ttft_p50_ms": 1e3 * serve_cell.percentile(m["ttfts"], 50),
            "ttft_p95_ms": m["ttft_p95_ms"],
            "itl_p95_ms": m["itl_p95_ms"],
            "backlog_first_quarter": sum(q[:quarter]) / quarter,
            "backlog_last_quarter": sum(q[-quarter:]) / quarter,
            "backlog_max": max(q) if q else 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(common.ROOT / "src"))
    cell = common.cell_from_files(args.config, args.traffic)
    common.setup_compile_cache()
    try:
        device = common.device_info(cell.chips)
    except common.NoChip as e:
        log(f"no result: {e}")
        return 2
    common.set_log_prefix(device)
    import gc
    for rate in (float(r) for r in args.rates.split(",")):
        out = sweep_rate(cell, rate, args.seconds, args.seed)
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
