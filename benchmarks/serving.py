"""Serving throughput/latency: continuous batching vs the static engine, and
prefix caching on a shared-system-prompt trace.

A Poisson arrival trace of requests with heterogeneous generation lengths is
served by both engines at several request rates. The static engine groups
arrivals into fixed batches and decodes each batch in lock-step until its
*longest* member finishes — short requests burn decode steps producing tokens
nobody asked for. The continuous engine recycles a finished slot into the
next queued request immediately, so aggregate tokens/sec tracks useful work.

The second section is the paper's memory-bound serving story end to end: a
trace whose requests share one long system prompt (the production shape —
millions of users, one template) is served with the prefix cache off and on.
With it on, the shared prompt's K/V pages are computed once and refcounted
into every request's page table, so prefill tokens computed, time-to-first-
token, and peak pages-in-use all drop.

The third section prices stochastic decoding: the same trace served greedy
and with per-request temperature/top-k/top-p (chat-shaped traffic) through
the sort-free sampler, so the sampler's overhead shows up as a tok/s delta
instead of a guess.

The ``families`` section serves the non-dense architectures the decode-state
protocol opened up — pure-SSM mamba2, hybrid jamba, and token-choice
deepseek-moe smoke configs — through the same continuous engine, recording
tok/s, latency, and the per-family prefix-cache gate (forced off, with the
recorded reason, for SSM-bearing archs). ``tools/check_bench.py`` requires
this section in the baseline.

The ``multistep`` section prices the multi-step compiled decode loop: the
same mixed greedy/sampled trace served at ``decode_steps`` N in {1, 4, 16}.
It records tok/s, host dispatches per decode token (hard-bounded in-bench at
``< 1.1/N`` — a deterministic count), the host-sync reduction factor, and
``diverged_streams`` vs N=1 (the determinism contract pins it at 0).
``tools/check_bench.py`` requires this section too.

With ``--tp N`` (N > 1; needs N devices — on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) a fourth section
serves the same trace through the tensor-parallel engine: tok/s vs tp=1, the
number of diverged token streams (0 expected), per-device pages-in-use /
KV bytes under head sharding, and the analytic all-reduce wire bytes.

    PYTHONPATH=src python -m benchmarks.serving [--arch llama3.2-3b] \
        [--json serving_bench.json] [--tp 2]

Emits ``name,us_per_call,derived`` CSV rows like the other benchmarks, plus a
human-readable summary with p50/p99 inter-token latency; ``--json`` writes
the full result dict (CI uploads it as an artifact, and
``tools/check_bench.py`` gates it against ``benchmarks/baselines/``).
An engine error — any request finishing with an ``"error"`` result the trace
did not ask for, or an engine exception — exits nonzero WITHOUT writing the
JSON artifact, so CI never uploads (or gates on) a partial result as if it
were healthy.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import (ContinuousEngine, Request, SamplingParams,
                           pages_needed)

from .common import emit

PAGE_SIZE = 16

# trace_stats() of every continuous engine the current run() built, so the
# summary can assert the jit caches stayed closed across ALL sections —
# a retrace anywhere in the bench shows up as nonzero ``recompiles.excess``
# and tools/check_bench.py gates on it
_ENGINE_STATS: list = []


class EngineError(RuntimeError):
    """A serving run produced error results the trace did not ask for."""


def chat_sampling(uid: int) -> SamplingParams:
    """The canonical chat-shaped sampling settings every stochastic section
    uses (seed = uid so streams are reproducible AND distinct per request);
    one definition, so the sampled and tp sections price the same traffic."""
    return SamplingParams(temperature=0.8, top_k=40, top_p=0.95, seed=uid)


def make_trace(n_requests, rate, *, prompt_len=32, gen_range=(8, 64), seed=0):
    """Poisson arrivals (exponential inter-arrival at ``rate`` req/s) with
    ragged generation lengths."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests)) \
        if np.isfinite(rate) else np.zeros(n_requests)
    prompts = rng.integers(5, 500, (n_requests, prompt_len))
    gens = rng.integers(gen_range[0], gen_range[1] + 1, n_requests)
    return [Request(uid=i, prompt=[int(t) for t in prompts[i]],
                    max_new_tokens=int(gens[i]), arrival=float(arrivals[i]))
            for i in range(n_requests)]


def make_shared_prefix_trace(n_requests, *, system_len=50, user_range=(4, 12),
                             gen_range=(8, 24), seed=0):
    """Every request = one shared system prompt + a short unique user suffix
    (the template-serving shape prefix caching exists for). The default
    system_len is deliberately NOT page-aligned, so the shared tail page
    exercises the copy-on-write path too."""
    rng = np.random.default_rng(seed)
    system = [int(t) for t in rng.integers(5, 500, system_len)]
    reqs = []
    for i in range(n_requests):
        user = [int(t) for t in
                rng.integers(5, 500, int(rng.integers(*user_range)))]
        reqs.append(Request(uid=i, prompt=system + user,
                            max_new_tokens=int(rng.integers(gen_range[0],
                                                            gen_range[1] + 1))))
    return reqs


def run_static(model, params, requests, batch_size):
    """Fixed-batch baseline: arrivals grouped into batches of ``batch_size``;
    each batch waits for its last arrival, prefills together, and decodes
    until its longest generation finishes."""
    arch = model.arch
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    t0 = time.perf_counter()
    token_times = {r.uid: [] for r in requests}
    for start in range(0, len(requests), batch_size):
        group = requests[start:start + batch_size]
        b = len(group)
        plen = len(group[0].prompt)
        max_gen = max(r.max_new_tokens for r in group)
        # the batch cannot start before its last member arrives
        while time.perf_counter() - t0 < max(r.arrival for r in group):
            time.sleep(1e-4)
        caches = model.init_caches(None, b, plen + max_gen)
        tokens_np = np.asarray([r.prompt for r in group], np.int32)
        logits, caches = prefill(params, caches,
                                 {"tokens": jnp.asarray(tokens_np)})
        toks = jnp.argmax(logits[:, -1], axis=-1)
        for i, r in enumerate(group):
            token_times[r.uid].append(time.perf_counter() - t0)
        for step in range(max_gen - 1):
            db = {"tokens": toks[:, None],
                  "positions": jnp.full((b,), plen + step, jnp.int32)}
            logits, caches = decode(params, caches, db)
            toks = jnp.argmax(logits[:, -1], axis=-1)
            # jaxlint: allow[hot-host-sync] intentional: per-token latency
            # timestamps are the point of this benchmark loop
            toks.block_until_ready()
            now = time.perf_counter() - t0
            for i, r in enumerate(group):
                if step + 1 < r.max_new_tokens:   # useful token, not waste
                    token_times[r.uid].append(now)
    wall = time.perf_counter() - t0
    return token_times, wall


def run_continuous(model, params, requests, slots, *, prefix_cache=False,
                   tp=1, warmup=None, decode_steps=1, spare_pages=0):
    """Serve ``requests`` through one ContinuousEngine sized for the trace.
    Returns (uid -> token_times, full results dict, wall seconds, engine) —
    every section (rates / shared-prefix / sampled / tp) goes through here
    so the pool-sizing math lives in exactly one place. Error results are an
    engine failure (these traces all fit the pool): raise instead of letting
    the bench summarize a partial run as healthy.

    ``warmup`` (a list of Requests) is served through the same engine
    BEFORE the timer starts: sections that price a *delta* between engine
    configurations (sampled vs greedy) pass a warmup trace hitting every
    jit variant the timed trace needs, so the delta compares steady-state
    serving instead of being dominated by one-time trace + XLA-compile
    cost on a short trace."""
    max_seq = max(len(r.prompt) + r.max_new_tokens for r in requests)
    num_pages = slots * pages_needed(max_seq + 1, PAGE_SIZE) + 2 + spare_pages
    engine = ContinuousEngine(model, params, num_slots=slots,
                              num_pages=num_pages, page_size=PAGE_SIZE,
                              max_seq_len=max_seq + PAGE_SIZE,
                              prefix_cache=prefix_cache, tp=tp,
                              decode_steps=decode_steps)
    if warmup:
        wres = engine.run(list(warmup))
        werrors = {uid: r["error"] for uid, r in wres.items()
                   if "error" in r}
        if werrors:
            raise EngineError(f"warmup returned error results: {werrors}")
    t0 = time.perf_counter()
    results = engine.run(requests)
    wall = time.perf_counter() - t0
    errors = {uid: r["error"] for uid, r in results.items() if "error" in r}
    if errors:
        raise EngineError(f"engine returned error results: {errors}")
    times = {uid: r["token_times"] for uid, r in results.items()}
    _ENGINE_STATS.append(engine.trace_stats())
    return times, results, wall, engine


def summarize(token_times, wall):
    all_tokens = sum(len(v) for v in token_times.values())
    gaps = []
    for times in token_times.values():
        gaps.extend(np.diff(times))
    gaps = np.asarray(gaps) if gaps else np.zeros(1)
    return {"tok_s": all_tokens / wall,
            "p50_ms": float(np.percentile(gaps, 50) * 1e3),
            "p99_ms": float(np.percentile(gaps, 99) * 1e3)}


def mean_ttft_ms(token_times, requests):
    arrivals = {r.uid: r.arrival for r in requests}
    ttfts = [times[0] - arrivals[uid]
             for uid, times in token_times.items() if times]
    return float(np.mean(ttfts) * 1e3) if ttfts else float("nan")


def run_rates(model, params, n_requests, slots, rates, results):
    for rate in rates:
        trace = make_trace(n_requests, rate)
        tag = "inf" if np.isinf(rate) else f"{rate:g}"
        st_times, st_wall = run_static(model, params, trace, slots)
        st = summarize(st_times, st_wall)
        ct_times, _, ct_wall, _ = run_continuous(model, params, trace, slots)
        ct = summarize(ct_times, ct_wall)
        emit(f"serve_static_rate{tag}", st_wall * 1e6 / max(1, n_requests),
             f"{st['tok_s']:.1f}tok/s_p50={st['p50_ms']:.1f}ms_"
             f"p99={st['p99_ms']:.1f}ms")
        emit(f"serve_continuous_rate{tag}", ct_wall * 1e6 / max(1, n_requests),
             f"{ct['tok_s']:.1f}tok/s_p50={ct['p50_ms']:.1f}ms_"
             f"p99={ct['p99_ms']:.1f}ms")
        speedup = ct["tok_s"] / max(st["tok_s"], 1e-9)
        print(f"[serving] rate={tag} req/s: static {st['tok_s']:.1f} tok/s "
              f"vs continuous {ct['tok_s']:.1f} tok/s "
              f"({speedup:.2f}x aggregate throughput)")
        results["rates"][tag] = {"static": st, "continuous": ct,
                                 "speedup": speedup}


def run_shared_prefix(model, params, n_requests, slots, results):
    trace = make_shared_prefix_trace(n_requests)
    out = {}
    for prefix_cache in (False, True):
        times, _, wall, engine = run_continuous(model, params, trace, slots,
                                                prefix_cache=prefix_cache)
        tag = "on" if prefix_cache else "off"
        out[tag] = {
            **summarize(times, wall),
            "ttft_ms": mean_ttft_ms(times, trace),
            "prefill_tokens": engine.prefill_tokens,
            "cached_prefill_tokens": engine.cached_prefill_tokens,
            "cow_copies": engine.cow_copies,
            # pages the drained engine still holds = the resident prefix cache
            "pages_in_use_after_drain": engine.pages_in_use,
            "live_kv_tokens_after_drain": engine.live_kv_tokens,
        }
        emit(f"serve_prefix_{tag}", wall * 1e6 / max(1, n_requests),
             f"prefill_tok={engine.prefill_tokens}_"
             f"ttft={out[tag]['ttft_ms']:.1f}ms")
    off, on = out["off"], out["on"]
    print(f"[serving] shared-prefix trace ({n_requests} requests): "
          f"prefill tokens {off['prefill_tokens']} -> {on['prefill_tokens']} "
          f"({off['prefill_tokens'] / max(on['prefill_tokens'], 1):.1f}x "
          f"fewer computed), "
          f"mean TTFT {off['ttft_ms']:.1f} -> {on['ttft_ms']:.1f} ms, "
          f"{on['cached_prefill_tokens']} tokens served from cache, "
          f"{on['cow_copies']} CoW tail copies")
    results["shared_prefix"] = out


def run_sampled(model, params, n_requests, slots, results):
    """Same trace served greedy and sampled (per-request temperature/top-k/
    top-p, seed = uid): tok/s and inter-token latency for each, the
    sampler's relative overhead over greedy, and how many streams actually
    diverged from greedy (at these settings nearly all should). Each engine
    serves a tiny warmup trace before its timed pass (see
    ``run_continuous``): the overhead percentage prices the sampler math per
    step, not the one-time compile of the sampled jit variants."""
    base = make_trace(n_requests, float("inf"))
    sampled = [Request(uid=r.uid, prompt=r.prompt,
                       max_new_tokens=r.max_new_tokens, arrival=r.arrival,
                       sampling=chat_sampling(r.uid))
               for r in base]

    def warmup_trace(stochastic):
        # two short requests whose prompts span >1 prefill chunk: together
        # they hit every jit variant the timed trace uses (chunked +
        # final-chunk prefill, decode, each with this engine's sampling
        # settings), so the timed pass below measures steady-state serving
        rng = np.random.default_rng(4242)
        prompts = rng.integers(5, 500, (2, 72))
        return [Request(uid=9000 + i, prompt=[int(t) for t in prompts[i]],
                        max_new_tokens=6,
                        sampling=chat_sampling(9000 + i) if stochastic
                        else SamplingParams())
                for i in range(2)]

    out = {}
    tokens = {}
    for tag, trace in (("greedy", base), ("sampled", sampled)):
        times, res, wall, _ = run_continuous(
            model, params, trace, slots, prefix_cache=True,
            warmup=warmup_trace(trace is sampled))
        tokens[tag] = {uid: r["tokens"] for uid, r in res.items()}
        out[tag] = summarize(times, wall)
        emit(f"serve_{tag}_decode", wall * 1e6 / max(1, n_requests),
             f"{out[tag]['tok_s']:.1f}tok/s_p50={out[tag]['p50_ms']:.1f}ms")
    out["sampler_overhead_pct"] = 100.0 * (
        out["greedy"]["tok_s"] / max(out["sampled"]["tok_s"], 1e-9) - 1.0)
    out["diverged_requests"] = sum(
        1 for uid in tokens["greedy"]
        if tokens["greedy"][uid] != tokens["sampled"][uid])
    print(f"[serving] sampled trace ({n_requests} requests, temp=0.8 "
          f"top_k=40 top_p=0.95): greedy {out['greedy']['tok_s']:.1f} tok/s "
          f"vs sampled {out['sampled']['tok_s']:.1f} tok/s "
          f"({out['sampler_overhead_pct']:.1f}% sampler overhead), "
          f"{out['diverged_requests']}/{n_requests} streams diverged from "
          f"greedy")
    results["sampled"] = out


def run_families(n_requests, slots, results):
    """Hybrid + MoE serving section: the decode-state protocol end to end.

    Serves the same ragged greedy trace through the continuous engine on
    three non-dense smoke archs — mamba2 (pure SSM: constant-size per-slot
    state, no pages in HBM), jamba (hybrid: 1 attention layer per 8, paged
    KV + slot state side by side), and deepseek-moe (token-choice MoE) —
    and records tok/s, inter-token latency, prefill accounting, and the
    per-family prefix-cache gate (SSM-bearing archs force it off; the
    engine records the reason instead of silently no-op'ing)."""
    out = {}
    for name in ("mamba2-1.3b", "jamba-v0.1-52b", "deepseek-moe-16b"):
        arch = smoke_config(name)
        model = build_model(arch)
        params = model.init(jax.random.key(0))
        params = jax.tree.map(lambda p: p.astype(jnp.dtype(arch.dtype)),
                              params)
        trace = make_trace(n_requests, float("inf"), prompt_len=24,
                           gen_range=(8, 32), seed=5)
        times, _, wall, engine = run_continuous(model, params, trace, slots,
                                                prefix_cache=True)
        tag = name.split("-")[0]
        out[name] = {
            **summarize(times, wall),
            "family": arch.family,
            "prefill_tokens": engine.prefill_tokens,
            "prefix_cache": ("off: " + engine.prefix_cache_off_reason
                             if engine.prefix_cache_off_reason else "on"),
        }
        emit(f"serve_family_{tag}", wall * 1e6 / max(1, n_requests),
             f"{out[name]['tok_s']:.1f}tok/s_p50={out[name]['p50_ms']:.1f}ms")
        print(f"[serving] {name} ({arch.family}): "
              f"{out[name]['tok_s']:.1f} tok/s, "
              f"p50 {out[name]['p50_ms']:.1f} ms, "
              f"prefix cache {out[name]['prefix_cache'].split(':')[0]}")
    results["families"] = out


def run_multistep(model, params, n_requests, slots, results):
    """Multi-step compiled decode section: the same mixed greedy/sampled
    trace served at ``decode_steps`` N in {1, 4, 16}. N > 1 moves N decode
    iterations into one on-device ``lax.while_loop`` per host dispatch, so
    the section prices exactly what the tentpole claims: host dispatches per
    decode-emitted token must fall ~Nx (hard bound ``< 1.1 / N``, enforced
    here — it is a deterministic count, not a timing), throughput must not
    regress (``speedup_vs_n1``, gated relatively by check_bench), and token
    streams must stay bit-identical to N=1 (``diverged_streams``, pinned at
    0). Each engine serves a mixed warmup trace first so the timed pass
    compares steady-state serving, not one-time trace+compile cost.

    The trace is decode-heavy (generation lengths 32..64, several horizons
    each): the loop's early exit is GLOBAL, so a request within N tokens of
    its budget truncates the whole dispatch — on traffic shorter than the
    horizon the 1.1/N bound is unattainable by design, and picking N above
    the typical remaining budget buys nothing (docs/SERVING.md covers the
    tuning trade-off)."""
    base = make_trace(n_requests, float("inf"), gen_range=(32, 64))
    trace = [Request(uid=r.uid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens, arrival=r.arrival,
                     sampling=chat_sampling(r.uid)
                     if r.uid % 2 else SamplingParams())
             for r in base]

    def warmup_trace():
        # one greedy + one sampled request spanning >1 prefill chunk: hits
        # the chunked/final prefill and both decode variants the timed
        # mixed trace needs, at this engine's horizon
        rng = np.random.default_rng(777)
        prompts = rng.integers(5, 500, (2, 72))
        return [Request(uid=9100 + i, prompt=[int(t) for t in prompts[i]],
                        max_new_tokens=6,
                        sampling=chat_sampling(9100 + i) if i
                        else SamplingParams())
                for i in range(2)]

    out = {}
    tokens = {}
    for n in (1, 4, 16):
        # prefix cache OFF + two spare pages per slot: the horizon
        # pre-allocator only takes FREE pages beyond its preemption reserve
        # and never evicts, so retained prompt pages (these random prompts
        # share nothing — the cache buys zero hits here) or a trace-exact
        # pool would truncate dispatches on page-budget exits instead of
        # letting them run their horizon
        times, res, wall, engine = run_continuous(
            model, params, trace, slots, prefix_cache=False, decode_steps=n,
            warmup=warmup_trace(), spare_pages=2 * slots)
        tokens[n] = {uid: r["tokens"] for uid, r in res.items()}
        # each request's FIRST token comes from its final prefill chunk;
        # everything after is emitted by decode dispatches
        decode_tokens = sum(len(t) for t in tokens[n].values()) - len(trace)
        dpt = engine.decode_dispatches / max(decode_tokens, 1)
        if n > 1 and dpt >= 1.1 / n:
            raise EngineError(
                f"decode_steps={n}: {engine.decode_dispatches} dispatches "
                f"for {decode_tokens} decode tokens = {dpt:.4f} "
                f"dispatches/token, above the 1.1/N={1.1 / n:.4f} bound — "
                "the loop is exiting early every dispatch")
        out[f"n{n}"] = {
            **summarize(times, wall),
            "decode_dispatches": engine.decode_dispatches,
            "decode_steps": engine.steps,
            "decode_tokens": decode_tokens,
            "dispatches_per_token": dpt,
            "exits": dict(engine.decode_exits),
        }
        emit(f"serve_multistep_n{n}", wall * 1e6 / max(1, n_requests),
             f"{out[f'n{n}']['tok_s']:.1f}tok/s_"
             f"{dpt:.3f}dispatch/tok")
    d1 = out["n1"]["decode_dispatches"]
    for n in (4, 16):
        out[f"n{n}"]["host_sync_reduction"] = d1 / max(
            out[f"n{n}"]["decode_dispatches"], 1)
        out[f"n{n}"]["speedup_vs_n1"] = (
            out[f"n{n}"]["tok_s"] / max(out["n1"]["tok_s"], 1e-9))
    out["diverged_streams"] = sum(
        1 for n in (4, 16) for uid in tokens[1]
        if tokens[1][uid] != tokens[n][uid])
    print(f"[serving] multistep trace ({n_requests} requests, mixed "
          f"greedy/sampled): "
          + ", ".join(
              f"N={n} {out[f'n{n}']['tok_s']:.1f} tok/s "
              f"({out[f'n{n}']['dispatches_per_token']:.3f} dispatch/tok)"
              for n in (1, 4, 16))
          + f"; host syncs cut {out['n4']['host_sync_reduction']:.1f}x at "
            f"N=4 / {out['n16']['host_sync_reduction']:.1f}x at N=16, "
            f"{out['diverged_streams']} diverged streams (must be 0)")
    results["multistep"] = out


def run_tp(model, params, n_requests, slots, tp, results):
    """Tensor-parallel section: the same mixed greedy/sampled trace served
    at tp=1 and tp=N. Streams must not diverge (head-sharded TP is an
    execution layout, not a model change); per-device pages/KV bytes and the
    analytic all-reduce wire bytes quantify what sharding buys and costs.

    Runs in fp32, like the cross-engine parity tests: at bf16 the psum's
    reassociated summation flips near-tied argmaxes of this random-init
    smoke model, which would conflate layout rounding noise with real
    divergence — ``diverged_streams`` is the health signal here, and 0 is
    the only healthy value.
    """
    if len(jax.devices()) < tp:
        raise EngineError(
            f"--tp {tp} needs {tp} devices, found {len(jax.devices())} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count)")
    params = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    model = build_model(dataclasses.replace(model.arch, dtype="float32"))
    base = make_trace(n_requests, float("inf"))
    trace = [Request(uid=r.uid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens, arrival=r.arrival,
                     sampling=chat_sampling(r.uid)
                     if r.uid % 2 else SamplingParams())
             for r in base]
    out = {"tp": tp}
    tokens = {}
    for degree in (1, tp):
        times, res, wall, engine = run_continuous(model, params, trace,
                                                  slots, prefix_cache=True,
                                                  tp=degree)
        tokens[degree] = {uid: r["tokens"] for uid, r in res.items()}
        tag = f"tp{degree}"
        out[tag] = summarize(times, wall)
        if degree > 1:
            out[tag].update(engine.tp_stats())
        emit(f"serve_{tag}_decode", wall * 1e6 / max(1, n_requests),
             f"{out[tag]['tok_s']:.1f}tok/s_p50={out[tag]['p50_ms']:.1f}ms")
    out["diverged_streams"] = sum(
        1 for uid in tokens[1] if tokens[1][uid] != tokens[tp][uid])
    tps = out[f"tp{tp}"]
    print(f"[serving] tp={tp} trace ({n_requests} requests): "
          f"tp1 {out['tp1']['tok_s']:.1f} tok/s vs tp{tp} "
          f"{tps['tok_s']:.1f} tok/s, "
          f"{out['diverged_streams']}/{n_requests} streams diverged, "
          f"{tps['collective_bytes_per_device'] / 1e6:.2f} MB all-reduced "
          f"and {tps['per_device']['kv_bytes'] / 1e6:.2f} MB KV "
          f"({tps['per_device']['pages_in_use']} pages) per device")
    results["tensor_parallel"] = out


def run(arch_name="llama3.2-3b", n_requests=16, slots=4,
        rates=(4.0, 16.0, float("inf")), json_path=None, tp=1,
        tp_only=False, sampled_only=False, multistep_only=False) -> dict:
    arch = smoke_config(arch_name)
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    params = jax.tree.map(lambda p: p.astype(jnp.dtype(arch.dtype)), params)

    results = {"arch": arch_name, "n_requests": n_requests, "slots": slots,
               "backend": jax.default_backend(), "rates": {}}
    _ENGINE_STATS.clear()
    if sampled_only:
        run_sampled(model, params, n_requests, slots, results)
    elif multistep_only:
        run_multistep(model, params, n_requests, slots, results)
    elif not tp_only:
        run_rates(model, params, n_requests, slots, rates, results)
        run_shared_prefix(model, params, n_requests, slots, results)
        run_sampled(model, params, n_requests, slots, results)
        run_families(n_requests, slots, results)
        run_multistep(model, params, n_requests, slots, results)
    if tp > 1:
        run_tp(model, params, n_requests, slots, tp, results)
    # jit-cache closure census across every engine the run built: ``excess``
    # counts traces beyond one-per-variant (i.e. recompiles after warmup)
    # and must be 0 — check_bench gates on it with direction "zero"
    results["recompiles"] = {
        "engines": len(_ENGINE_STATS),
        "variants": sum(s["variants"] for s in _ENGINE_STATS),
        "traces": sum(s["traces"] for s in _ENGINE_STATS),
        "excess": sum(s["excess"] for s in _ENGINE_STATS),
    }
    rc = results["recompiles"]
    print(f"[serving] recompiles: {rc['engines']} engine(s), "
          f"{rc['variants']} jit variant(s), {rc['traces']} trace(s) — "
          f"{rc['excess']} recompile(s) after warmup")
    if json_path:
        with open(json_path, "w") as f:
            json.dump(results, f, indent=2)
        print(f"[serving] wrote {json_path}")
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--tp", type=int, default=1,
                    help="add a tensor-parallel section at this degree "
                         "(needs that many devices)")
    ap.add_argument("--tp-only", action="store_true",
                    help="run ONLY the tensor-parallel section (it serves "
                         "tp=1 itself for the comparison) — the multidevice "
                         "CI job uses this to avoid re-running the "
                         "single-device sections the tier1 job covers")
    ap.add_argument("--sampled-only", action="store_true",
                    help="run ONLY the sampled-traffic section (greedy vs "
                         "sampled) — the nightly CI job "
                         "uses this with a larger trace to watch the "
                         "sampler tax without re-running the full bench")
    ap.add_argument("--multistep-only", action="store_true",
                    help="run ONLY the multi-step compiled decode section "
                         "(decode_steps N in {1,4,16}) — the nightly CI job "
                         "uses this with a larger trace to watch host-sync "
                         "reduction without re-running the full bench")
    ap.add_argument("--json", default="",
                    help="also write the full results dict to this path")
    args = ap.parse_args()
    if args.tp_only and args.tp <= 1:
        ap.error("--tp-only requires --tp > 1")
    if sum((args.tp_only, args.sampled_only, args.multistep_only)) > 1:
        ap.error("--tp-only/--sampled-only/--multistep-only are mutually "
                 "exclusive")
    print("name,us_per_call,derived")
    try:
        run(args.arch, args.requests, args.slots, json_path=args.json or None,
            tp=args.tp, tp_only=args.tp_only, sampled_only=args.sampled_only,
            multistep_only=args.multistep_only)
    except Exception as e:  # noqa: BLE001 — any engine failure must fail CI
        # no JSON is written on this path: a partial artifact uploaded by CI
        # reads as a healthy run with silently missing sections
        print(f"[serving] ENGINE ERROR: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
