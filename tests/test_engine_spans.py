"""The serving engine's host spans and program names, on the CPU at a tiny
size: a few requests served under ``jax.profiler`` and the trace read back
with ``ProfileData``.

Every loop iteration is one ``engine.step`` span holding non-overlapping
phase spans; each decode dispatch is one ``engine.decode`` whose ``slots``
and ``kv_tokens`` are the scheduler's own counts, and its
``engine.decode_wait`` carries the blocks the decode-attention kernel walks
(``kv_blocks``); the prefill path and the
multi-step path record theirs; each jitted program compiles under its own
name; and the token streams are the same with the profiler on and off. A
MoE stack's ``engine.emit`` carries the step's expert counters
(``expert_rows``, ``experts_touched``); a dense stack's carries none.
"""
import dataclasses
import glob
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import smoke_config
from repro.kernels.decode_attention import ops
from repro.models import build_model
from repro.models import moe as moe_lib
from repro.serving import ContinuousEngine, Request
from repro.serving.sampling import SamplingParams

PHASES = ("engine.admit", "engine.prefill", "engine.prefill_wait",
          "engine.schedule", "engine.decode", "engine.decode_wait",
          "engine.emit")
HORIZONS = (1, 4)


def _requests(vocab):
    """Five requests over three slots: ragged prompts (one of three 16-token
    chunks), mixed sampling, and one prompt that extends another past a
    partial page, so its admission copies that page."""
    rng = np.random.default_rng(3)

    def prompt(n):
        return [int(t) for t in rng.integers(5, vocab, n)]

    base = prompt(10)
    prompts = [base, prompt(40), base + prompt(3), prompt(7), prompt(21)]
    samplings = [SamplingParams(), SamplingParams(temperature=0.8, seed=11),
                 SamplingParams(temperature=0.9, top_k=8, top_p=0.9, seed=12)]
    return [Request(uid=i, prompt=p, max_new_tokens=4 + i,
                    sampling=samplings[i % 3])
            for i, p in enumerate(prompts)]


def _engine(model, params, horizon):
    return ContinuousEngine(model, params, num_slots=3, num_pages=64,
                            page_size=4, max_seq_len=64,
                            decode_steps=horizon)


def _record_dispatches(engine):
    """(decoded slots, their cached tokens) from the scheduler at every
    decode dispatch, and the cached tokens of each decoded slot."""
    calls, lens = [], []

    def wrap(make):
        def make_recorded(sampled, filtered):
            fn = make(sampled, filtered)

            def call(*args):
                sched = engine.scheduler
                slots = [s for s in sched.running
                         if not engine._prefill_pending(s)]
                lens.append([int(sched.cache.seq_lens[s]) for s in slots])
                calls.append((len(slots), sum(lens[-1])))
                return fn(*args)
            return call
        return make_recorded

    engine._decode_fn = wrap(engine._decode_fn)
    engine._decode_multi_fn = wrap(engine._decode_multi_fn)
    return calls, lens


def _record_modules(engine):
    """jit-cache key -> the ``HloModule`` line of the program compiled
    under it, taken at its first call."""
    modules = {}
    build = engine._build

    def build_recorded(impl, in_specs, out_specs, donate, key=()):
        fn = build(impl, in_specs, out_specs, donate, key=key)

        def call(*args):
            if key not in modules:
                text = fn.lower(*args).compile().as_text()
                modules[key] = text.split(",", 1)[0]
            return fn(*args)
        return call

    engine._build = build_recorded
    return modules


def _engine_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.append((e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns),
                                  dict(e.stats)))
    return sorted(spans, key=lambda s: s[1])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    arch = smoke_config("llama3.2-3b")
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    reqs = _requests(arch.vocab_size)
    out = {}
    for horizon in HORIZONS:
        engine = _engine(model, params, horizon)
        calls, lens = _record_dispatches(engine)
        trace_dir = tmp_path_factory.mktemp(f"trace{horizon}")
        jax.profiler.start_trace(str(trace_dir))
        try:
            traced = engine.run(list(reqs))
        finally:
            jax.profiler.stop_trace()
        plain = _engine(model, params, horizon)
        modules = _record_modules(plain)
        untraced = plain.run(list(reqs))
        out[horizon] = dict(
            engine=engine, calls=calls, lens=lens,
            spans=_engine_spans(trace_dir),
            traced={u: r["tokens"] for u, r in traced.items()},
            untraced={u: r["tokens"] for u, r in untraced.items()},
            modules=modules, uids=sorted(r.uid for r in reqs))
    return out


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("horizon", HORIZONS)
def test_every_span_appears_and_phases_nest_in_steps(served, horizon):
    spans = served[horizon]["spans"]
    steps = _named(spans, "engine.step")
    assert [s[3]["i"] for s in steps] == list(range(len(steps)))
    assert {s[0] for s in spans} == {"engine.step", *PHASES}
    for name, start, end, _ in spans:
        if name == "engine.step":
            continue
        holders = [s for s in steps if s[1] <= start and end <= s[2]]
        assert len(holders) == 1, (name, start)
    # the phases of one step never overlap
    for step in steps:
        phases = [s for s in spans if s[0] != "engine.step"
                  and step[1] <= s[1] and s[2] <= step[2]]
        for a, b in zip(phases, phases[1:]):
            assert a[2] <= b[1], (a, b)


@pytest.mark.parametrize("horizon", HORIZONS)
def test_one_decode_span_per_dispatch(served, horizon):
    s = served[horizon]
    engine, spans = s["engine"], s["spans"]
    assert len(_named(spans, "engine.decode")) == engine.decode_dispatches
    assert len(_named(spans, "engine.decode_wait")) == \
        engine.decode_dispatches
    assert len(_named(spans, "engine.emit")) == engine.decode_dispatches
    assert engine.decode_dispatches > 0
    if horizon > 1:
        assert engine.steps > engine.decode_dispatches


@pytest.mark.parametrize("horizon", HORIZONS)
def test_decode_span_args_are_the_schedulers_counts(served, horizon):
    s = served[horizon]
    decodes = _named(s["spans"], "engine.decode")
    got = [(d[3]["slots"], d[3]["kv_tokens"]) for d in decodes]
    assert got == s["calls"]
    assert {d[3]["horizon"] for d in decodes} == {horizon}
    assert max(slots for slots, _ in got) >= 2      # batches of several


@pytest.mark.parametrize("horizon", HORIZONS)
def test_decode_wait_carries_the_attention_walk(served, horizon):
    """Each dispatch's ``engine.decode_wait`` carries ``kv_blocks``: the
    blocks the paged decode-attention kernel walks in each layer of the
    dispatch's first step, one for every started block of a decoded slot's
    cache plus the token it writes, at the kernel's own block size."""
    s = served[horizon]
    engine, arch = s["engine"], s["engine"].arch
    block = engine.page_size * ops.pages_per_block(
        engine.page_size, arch.num_kv_heads * arch.resolved_head_dim
        * jnp.dtype(arch.dtype).itemsize, engine.max_pages_per_seq)
    assert engine.decode_block_tokens == block
    waits = _named(s["spans"], "engine.decode_wait")
    assert [w[3]["kv_blocks"] for w in waits] == [
        sum(math.ceil((n + 1) / block) for n in lens) for lens in s["lens"]]


@pytest.mark.parametrize("horizon", HORIZONS)
def test_prefill_and_admission_spans_match_the_counters(served, horizon):
    s = served[horizon]
    engine, spans = s["engine"], s["spans"]
    prefills = _named(spans, "engine.prefill")
    assert sum(p[3]["tokens"] for p in prefills) == engine.prefill_tokens
    finals = [p for p in prefills if p[3]["final"]]
    assert len(finals) == engine.prefills == len(s["uids"])
    assert len(prefills) > len(finals)          # the 40-token prompt
    assert len(_named(spans, "engine.prefill_wait")) == engine.prefills
    assert sorted(p[3]["uid"] for p in finals) == s["uids"]
    admits = _named(spans, "engine.admit")
    assert sorted(a[3]["uid"] for a in admits) == s["uids"]
    assert engine.cow_copies == 1


@pytest.mark.parametrize("horizon", HORIZONS)
def test_tokens_identical_with_profiler_on_and_off(served, horizon):
    s = served[horizon]
    assert s["traced"] == s["untraced"]
    assert all(len(t) == 4 + u for u, t in s["traced"].items())


def test_engine_programs_compile_under_their_names(served):
    names = {}
    for horizon in HORIZONS:
        for key, module in served[horizon]["modules"].items():
            kind = "loop" if key[0] == "decode" and len(key) == 6 \
                else key[0]
            names.setdefault(kind, set()).add(module)
    assert names == {"decode": {"HloModule jit_serve_decode_step"},
                     "loop": {"HloModule jit_serve_decode_loop"},
                     "prefill": {"HloModule jit_serve_prefill_chunk"},
                     "copy": {"HloModule jit_serve_copy_page"}}


def test_decode_span_args_stay_pinned(served):
    """``engine.decode`` carries exactly ``slots``, ``kv_tokens`` and
    ``horizon`` (the benchmark's trace readers pin them), and a dense
    stack's ``engine.emit`` no expert counters."""
    for horizon in HORIZONS:
        spans = served[horizon]["spans"]
        for d in _named(spans, "engine.decode"):
            assert set(d[3]) == {"slots", "kv_tokens", "horizon"}, d
        for e in _named(spans, "engine.emit"):
            assert not {"expert_rows", "experts_touched"} & set(e[3]), e


def test_moe_emit_carries_the_routers_expert_counts(tmp_path, monkeypatch):
    """For a MoE smoke stack that holds 2 of its 4 routed experts, each
    ``engine.emit`` carries the step's token-to-held-expert assignments and
    the held experts with at least one: a host recount from the router's
    top-k of the slots the step decoded (empty slots route nowhere)."""
    arch = smoke_config("deepseek-moe-16b-ep4")
    arch = dataclasses.replace(arch, dtype="float32", param_dtype="float32")
    moe = arch.moe
    assert (moe.held_count, moe.num_experts) == (2, 4)
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    engine = _engine(model, params, 1)
    decoded = []
    make = engine._decode_fn

    def make_recorded(sampled, filtered):
        fn = make(sampled, filtered)

        def call(*args):
            decoded.append([s for s in engine.scheduler.running
                            if not engine._prefill_pending(s)])
            return fn(*args)
        return call
    engine._decode_fn = make_recorded
    routed = []
    route = moe_lib.route

    def route_recorded(logits, moe_cfg):
        top_w, top_ids = route(logits, moe_cfg)
        if logits.shape[:2] == (engine.num_slots, 1):     # a decode step
            jax.debug.callback(lambda ids: routed.append(np.asarray(ids)),
                               top_ids, ordered=True)
        return top_w, top_ids
    monkeypatch.setattr(moe_lib, "route", route_recorded)
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.run(_requests(arch.vocab_size))
    finally:
        jax.profiler.stop_trace()
    emits = _named(_engine_spans(tmp_path), "engine.emit")
    n_moe = arch.num_layers - moe.first
    assert len(routed) == n_moe * len(decoded) == n_moe * len(emits)
    want = []
    for i, slots in enumerate(decoded):
        rows, touched = 0, 0
        for ids in routed[i * n_moe:(i + 1) * n_moe]:
            ids = ids[slots, 0]
            held = ids[ids < moe.held_count]
            rows += held.size
            touched += len(np.unique(held))
        want.append((rows, touched))
    got = [(e[3]["expert_rows"], e[3]["experts_touched"]) for e in emits]
    assert got == want
    assert sum(r for r, _ in got) > 0
    assert any(r < len(s) * moe.top_k for (r, _), s in zip(got, decoded))
