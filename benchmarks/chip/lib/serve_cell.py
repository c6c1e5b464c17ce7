"""A serving cell: ``ContinuousEngine.run`` over the cell's trace, with the
engine's defaults (one decode step per dispatch, fused decode, prefix
cache) and the sanitizer off.

``run`` replays a whole trace and has no way to stop early, and the
benchmark may not change the program. So the cell hands the engine its own
clock (``time_fn``), which opens the window once the engine has prefilled
the traffic's ``in_flight`` requests (at its first reading where there are
none): a backlog's slots are then full, and the fill counts as set-up. The
clock ends the run by raising once the window is over: at once for a
backlog, and for open-loop arrivals once every request due in the window
has its first token (or ``grace_seconds`` later). The tokens and their
times are then read from the scheduler's sequences: those still running,
and those that finished, which the cell records as the scheduler finishes
them. A step-level engine API would make this unnecessary.

``correct`` compares what the timed path served: a sample, drawn from the
seed, of the greedy requests served in the window, with the one that got
the most tokens among them. The plain reference runs each prompt with its
served tokens through a float32 forward pass; ``logit_gap`` is the widest
gap by which a served token's reference logit lies below the reference's
best logit at that position.
"""
from __future__ import annotations

import gc
import math
import time
from typing import Dict, List

import numpy as np

from . import common, traffic as traffic_lib, weights
from .common import Check, RunResult, log, span


class WindowClosed(Exception):
    """Raised through ``engine.run`` by the cell's clock."""


class Record:
    """One request as the cell saw it."""

    def __init__(self, req: traffic_lib.TrafficRequest):
        self.req = req
        self.tokens: List[int] = []
        self.times: List[float] = []


class Clock:
    """The engine's ``time_fn``. The engine calls it first to set its own
    zero (``t0``); the window opens (``opened``) at the first reading at
    which the engine has finished ``open_after`` prefills."""

    def __init__(self, seconds, due, grace, engine, finished, open_after=0,
                 tracer=None):
        self.seconds, self.due, self.grace = seconds, due, grace
        self.engine, self.finished = engine, finished
        self.open_after, self.prefills0 = open_after, engine.prefills
        self.tracer = tracer
        self.t0 = self.opened = None

    @property
    def start(self) -> float:
        """The window's start on the engine's clock."""
        return self.opened - self.t0

    def _due_waiting(self) -> bool:
        got = {s.request.uid for s in self.finished if s.generated}
        got |= {s.request.uid for s in self.engine.scheduler.running.values()
                if s.generated}
        return any(uid not in got for uid in self.due)

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.t0 is None:
            self.t0 = t
        if self.opened is None:
            if self.engine.prefills - self.prefills0 < self.open_after:
                return t
            self.opened = t
        elapsed = t - self.opened
        if self.tracer is not None:
            self.tracer.tick(elapsed)
        if elapsed >= self.seconds:
            if elapsed < self.seconds + self.grace and self.due \
                    and self._due_waiting():
                return t
            raise WindowClosed
        return t


class Tracer:
    """A profiler trace of the window from ``start`` seconds for
    ``seconds``, started and stopped from the engine's clock inside
    ``engine.run``; its edges are marked by spans of their own
    (``bench.trace_start``, ``bench.trace_stop``)."""

    def __init__(self, directory, start, seconds):
        self.dir, self.start, self.seconds = directory, start, seconds
        self.started_at = self.stopped_at = None

    @property
    def active(self):
        return self.started_at is not None and self.stopped_at is None

    def tick(self, elapsed):
        import jax
        if self.started_at is None and elapsed >= self.start:
            jax.profiler.start_trace(str(self.dir))
            with span("trace_start"):
                self.started_at = time.perf_counter()
        elif self.active and elapsed >= self.start + self.seconds:
            self.stop()

    def stop(self):
        import jax
        with span("trace_stop"):
            self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()


def build_engine(cell, seed: int):
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.serving import ContinuousEngine
    arch = common.program_arch(cell.config)
    model = build_model(arch)
    structure = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make(structure, common.jax_key(seed),
                          jnp.dtype(arch.dtype))
    eng = cell.traffic["engine"]
    serving = cell.config["serving"]
    engine = ContinuousEngine(
        model, params, num_slots=eng["num_slots"], num_pages=eng["num_pages"],
        page_size=serving["page_size"],
        max_seq_len=serving["context_limit"], sanitize=False)
    return engine, arch


def to_requests(reqs, sampling: dict):
    from repro.serving import Request
    from repro.serving.sampling import SamplingParams
    out = []
    for r in reqs:
        sp = SamplingParams(seed=r.seed) if r.greedy else SamplingParams(
            temperature=sampling["temperature"], top_k=sampling["top_k"],
            top_p=sampling["top_p"], seed=r.seed)
        out.append(Request(uid=r.uid, prompt=list(r.prompt),
                           max_new_tokens=r.max_new_tokens,
                           arrival=r.arrival, sampling=sp))
    return out


def warm_up(engine, vocab: int, seed: int) -> None:
    """Compile every program the window can call: prefill chunks (partial,
    final greedy, final sampled), decode steps (all greedy, and sampled with
    filters) and the prefix cache's copy-on-write page copy."""
    from repro.serving import Request
    from repro.serving.sampling import SamplingParams
    rng = np.random.default_rng(np.random.SeedSequence((seed, 0x3A3)))
    chunk = engine.prefill_chunk
    slots = engine.num_slots

    def prompt(n):
        return rng.integers(5, vocab, n).tolist()

    sampled = SamplingParams(temperature=0.8, top_k=40, top_p=0.95, seed=1)
    mixed = [Request(uid=i, prompt=prompt(chunk + 5), max_new_tokens=3,
                     sampling=sampled if i % 2 else SamplingParams())
             for i in range(slots)]
    engine.run(mixed)
    engine.run([Request(uid=0, prompt=prompt(7), max_new_tokens=3)])
    shared = prompt(engine.page_size * 2 + 8)
    engine.run([Request(uid=i, prompt=shared + prompt(5), max_new_tokens=2)
                for i in range(2)])


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of the
    values at or below it."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def window_metrics(records: Dict[int, Record], seconds: float,
                   start: float = 0.0, open_loop: bool = True) -> dict:
    """Tokens, inter-token gaps and (for open-loop arrivals) times to the
    first token in the window (start, start + seconds] of the engine's
    clock."""
    end = start + seconds
    tokens = 0
    gaps: List[float] = []
    ttfts: List[float] = []
    for rec in records.values():
        times = [t for t in rec.times if start < t <= end]
        tokens += len(times)
        gaps += [b - a for a, b in zip(times, times[1:])]
        if open_loop and start <= rec.req.arrival <= end:
            ttfts.append(rec.times[0] - rec.req.arrival if rec.times
                         else math.inf)
    out = {"tokens": tokens, "gaps": gaps, "ttfts": ttfts,
           "output_tokens_per_s": tokens / seconds,
           "itl_p95_ms": 1e3 * percentile(gaps, 95)}
    if open_loop:
        out["ttft_p95_ms"] = 1e3 * percentile(ttfts, 95)
    return out


def decode_contexts(records: Dict[int, Record], lo: float, hi: float):
    """Per decode step in (lo, hi]: the context length of each sequence it
    advanced. A request's token i > 0 came from a decode step that read
    prompt + i tokens; steps are told apart by their shared token time."""
    steps: Dict[float, List[int]] = {}
    for rec in records.values():
        n = len(rec.req.prompt)
        for i, t in enumerate(rec.times):
            if i > 0 and lo < t <= hi:
                steps.setdefault(t, []).append(n + i)
    return steps


def pick_sample(records: Dict[int, Record], seed: int, check: dict):
    """Greedy requests that got tokens: the one with the most, then others
    drawn from the seed, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    greedy = sorted((r for r in records.values() if r.req.greedy and
                     r.tokens), key=lambda r: r.req.uid)
    if not greedy:
        return []
    first = max(greedy, key=lambda r: (len(r.tokens), -r.req.uid))
    rest = [r for r in greedy if r is not first]
    rng = np.random.default_rng(np.random.SeedSequence((seed, 0xC4EC)))
    rng.shuffle(rest)
    sample, total = [first], len(first.tokens)
    for r in rest:
        if total >= check["min_tokens"] or len(sample) >= check[
                "max_requests"]:
            break
        sample.append(r)
        total += len(r.tokens)
    return sample


def reference_params(cell, seed: int):
    """The served bf16 weights again from the seed, as float32."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    arch = common.program_arch(cell.config)
    structure = jax.eval_shape(build_model(arch).init, jax.random.key(0))
    dtype = jnp.dtype(arch.dtype)
    return jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(jnp.float32),
        weights.generate(structure, k, dtype)))(common.jax_key(seed))


def served_gaps(ref_mod, cfg, params, sample, mm=None) -> List[np.ndarray]:
    """Per sampled request: for each served token, how far its reference
    logit lies below the best reference logit at its position. With ``mm``
    (the control), the token compared is the one that ``mm``'s precision
    puts first, not the served one."""
    import jax.numpy as jnp
    out = []
    for rec in sample:
        n = len(rec.req.prompt)
        seq = list(rec.req.prompt) + rec.tokens
        ref = ref_mod.logits(cfg, params, seq[:-1])[n - 1:]
        if mm is None:
            picked = jnp.asarray(rec.tokens, jnp.int32)
        else:
            low = ref_mod.logits(cfg, params, seq[:-1], mm=mm)[n - 1:]
            picked = jnp.argmax(low, axis=-1)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, picked[:, None], -1)[:, 0]
        out.append(np.asarray(best - got))
    return out


def serve_window(engine, cell, requests, seconds, trace_dir=None):
    """Drive ``engine.run`` over ``requests`` for the window; -> records,
    counters before and after, and the tracer."""
    tr = cell.traffic
    finished: list = []
    sched = engine.scheduler
    finish = sched.finish

    def record_finish(seq):
        finished.append(seq)
        finish(seq)

    sched.finish = record_finish
    open_after = tr.get("in_flight", 0)
    if open_after and tr["arrival"] != "backlog":
        raise ValueError("in_flight is for a backlog: open-loop arrivals "
                         "are timed from the engine's start")
    due = {r.uid for r in requests if r.arrival <= seconds} \
        if tr["arrival"] != "backlog" else set()
    start = min(tr["trace_start_seconds"], seconds / 2)
    tracer = Tracer(trace_dir, start, min(tr["trace_seconds"],
                                          seconds - start)) \
        if trace_dir is not None else None
    clock = Clock(seconds, due, tr.get("grace_seconds", 0.0), engine,
                  finished, open_after, tracer)
    before = counters(engine)
    try:
        with span("engine_run"):
            engine.run(to_requests(requests, tr["sampling"]), time_fn=clock)
        log("[window] the trace ran out before the window closed")
    except WindowClosed:
        pass
    finally:
        live = engine.live_kv_tokens
        sched.finish = finish
        clock.engine = None     # let the caller free the engine
    if tracer is not None and tracer.active:
        tracer.stop()
    after = counters(engine)
    after["live_kv_tokens"] = live
    records = {r.uid: Record(r) for r in requests}
    for seq in finished + list(sched.running.values()):
        rec = records[seq.request.uid]
        rec.tokens = list(seq.generated)
        rec.times = list(seq.token_times)
    return records, before, after, clock, tracer


def decode_kernels(engine) -> dict:
    """op key -> Pallas kernel name in the compiled decode step the window
    ran (sampled with filters), for the per-layer readers."""
    import jax.numpy as jnp

    from . import trace as trace_lib
    z = jnp.zeros((engine.num_slots,), jnp.int32)
    table = jnp.zeros((engine.num_slots, engine.max_pages_per_seq), jnp.int32)
    text = engine._decode_fn(True, True).lower(
        engine.params, engine.pools, table, z, z,
        *engine._null_sampling).compile().as_text()
    return trace_lib.kernel_names(text)


def counters(engine) -> dict:
    return {"steps": engine.steps, "prefills": engine.prefills,
            "prefill_tokens": engine.prefill_tokens,
            "cached_prefill_tokens": engine.cached_prefill_tokens,
            "decode_dispatches": engine.decode_dispatches}


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        clock) -> RunResult:
    import jax

    from . import trace as trace_lib
    tr = cell.traffic
    engine, arch = build_engine(cell, seed)
    warm_up(engine, arch.vocab_size, seed)
    requests = traffic_lib.generate(tr, seed, arch.vocab_size)
    compiles0 = clock.compiles
    log(f"[setup] compile {clock.seconds:.3f}s over {clock.compiles} "
        f"compiles, persistent-cache hits {clock.hits}; slots "
        f"{engine.num_slots}, pages {tr['engine']['num_pages']}, fused "
        f"decode {engine.fused_decode}")
    trace_dir = None
    if trace:
        import shutil
        trace_dir = common.OUT_DIR / "trace" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    records, before, after, wclock, tracer = serve_window(
        engine, cell, requests, seconds, trace_dir)
    setup_s = wclock.opened - t_start
    w0, w1 = wclock.start, wclock.start + seconds
    in_window = clock.compiles - compiles0
    open_loop = tr["arrival"] != "backlog"
    m = window_metrics(records, seconds, w0, open_loop)
    served = sum(1 for r in records.values() if r.times and r.times[0]
                 <= w1)
    finished = sum(1 for r in records.values()
                   if len(r.times) == r.req.max_new_tokens
                   and w0 < r.times[-1] <= w1)
    pool = tr["engine"]["num_pages"] * cell.config["serving"]["page_size"]
    log(f"[setup] the window opened {w0:.3f}s into the engine's run, after "
        f"the {tr.get('in_flight', 0)} in-flight requests' prefills")
    log(f"[window] {m['tokens']} output tokens in {seconds}s; requests "
        f"due {len(wclock.due) or len(requests)}, with a first token by "
        f"the window's end {served}, finished in it {finished}, gaps "
        f"{len(m['gaps'])}; decode steps {after['steps'] - before['steps']}"
        f", prefills {after['prefills'] - before['prefills']}; live KV at "
        f"the close {after['live_kv_tokens']} tokens of a {pool}-token pool "
        f"({100.0 * after['live_kv_tokens'] / pool:.1f}%); compiles inside "
        f"the window: {in_window}")
    peak = common.memory_peak_bytes()
    num_slots = engine.num_slots
    kernels = decode_kernels(engine) if trace else {}
    common.free((engine.params, engine.pools))
    del engine
    gc.collect()

    ref_mod = common.reference_module(cell)
    sample = pick_sample(records, seed, tr["check"])
    params = reference_params(cell, seed)
    with jax.default_matmul_precision("highest"):
        gaps = served_gaps(ref_mod, cell.config, params, sample)
    del params
    gap = max((float(g.max()) for g in gaps), default=math.inf)
    log(f"[check] {len(sample)} greedy requests, "
        f"{sum(len(g) for g in gaps)} served tokens compared")
    # a backlog's requests wait by design; an open loop's request that never
    # got its first token has failed
    failed = sum(1 for t in m["ttfts"] if math.isinf(t))
    end_to_end = {k: m[k] for k in ("output_tokens_per_s", "itl_p95_ms",
                                    "ttft_p95_ms") if k in m}
    result = RunResult(
        end_to_end=dict(end_to_end, setup_s=setup_s),
        checks=[Check("logit_gap", gap, tr["limits"]["logit_gap"])],
        attempted=len(m["ttfts"]) if wclock.due else served,
        failed=failed,
        notes=[f"compiles inside the window: {in_window}"])
    result.layer_ctx = {
        "memory_peak_bytes": peak, "kind": "serve", "config": cell.config,
        "num_slots": num_slots, "seconds": seconds, "records": records,
        "decode_steps": decode_contexts(records, w0, w1)}
    if trace and tracer.started_at is not None:
        reduced = trace_lib.reduce(trace_lib.load(
            trace_lib.find_xplane(trace_dir)), between="bench.engine_run")
        result.layer_ctx.update(
            reduced=reduced, kernels=kernels,
            traced_decode_steps=decode_contexts(
                records, tracer.started_at - wclock.t0,
                tracer.stopped_at - wclock.t0))
        result.breakdown = reduced.breakdown()
        result.busy_s, result.window_s = reduced.busy_s, reduced.window_s
    return result
