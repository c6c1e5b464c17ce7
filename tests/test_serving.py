"""Continuous-batching engine: allocator invariants, scheduler recycling,
and exact greedy parity with the static engine."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import (ContinuousEngine, PageAllocator, Request,
                           Scheduler, pages_needed)
from repro.serving.kv_cache import NULL_PAGE


# ------------------------------------------------------------------ allocator ----

def test_allocator_alloc_free_roundtrip():
    a = PageAllocator(8)                       # pages 1..7 usable
    assert a.free_count == 7
    pages = a.alloc(3)
    assert len(set(pages)) == 3 and NULL_PAGE not in pages
    assert a.free_count == 4 and a.used_count == 3
    a.free(pages)
    assert a.free_count == 7 and a.used_count == 0


def test_allocator_never_double_allocates():
    a = PageAllocator(16)
    seen = set()
    held = []
    for _ in range(5):
        pages = a.alloc(3)
        assert not (seen & set(pages)), "page handed out twice while held"
        seen |= set(pages)
        held.append(pages)
    a.free(held.pop())
    more = a.alloc(3)                          # recycled ids are fine...
    assert not (set(more) & set().union(*held))  # ...but never held twice


def test_allocator_oom_refusal_is_all_or_nothing():
    a = PageAllocator(4)                       # 3 usable pages
    assert a.alloc(4) is None
    assert a.free_count == 3                   # refused alloc took nothing
    pages = a.alloc(3)
    assert pages is not None and a.alloc(1) is None
    a.free(pages)


def test_allocator_rejects_double_free_and_null_page():
    a = PageAllocator(8)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)
    with pytest.raises(ValueError):
        a.free([NULL_PAGE])


def test_allocator_refcounts_shared_pages():
    """A page shared N ways is stored once and survives until the last hold
    drops — the memory dedup prefix caching is built on."""
    a = PageAllocator(8)
    (pg,) = a.alloc(1)
    a.incref(pg)
    a.incref(pg)                               # three holds
    assert a.ref_count(pg) == 3
    assert a.used_count == 1                   # stored once
    a.free([pg])
    a.free([pg])
    assert a.used_count == 1 and a.free_count == 6   # still held
    a.free([pg])
    assert a.used_count == 0 and a.free_count == 7   # last hold: recycled
    with pytest.raises(ValueError):
        a.free([pg])
    with pytest.raises(ValueError):
        a.incref(pg)                           # can't share a dead page


def test_pages_needed():
    assert pages_needed(1, 16) == 1
    assert pages_needed(16, 16) == 1
    assert pages_needed(17, 16) == 2


# ------------------------------------------------------------------ scheduler ----

def _req(uid, plen=8, gen=4):
    return Request(uid=uid, prompt=list(range(5, 5 + plen)),
                   max_new_tokens=gen)


def test_scheduler_admission_by_free_pages():
    # 4 usable pages, page_size 4: an 8-token prompt needs 3 pages (ctx+1)
    s = Scheduler(num_slots=4, num_pages=5, page_size=4, max_pages_per_seq=8)
    s.submit(_req(0))
    s.submit(_req(1))
    seq = s.admit_next()
    assert seq is not None and seq.request.uid == 0
    assert s.admit_next() is None              # 1 free page < 3 needed
    s.finish(seq)
    assert s.admit_next().request.uid == 1     # pages recycled -> admitted


def test_scheduler_slot_recycling():
    s = Scheduler(num_slots=2, num_pages=64, page_size=4, max_pages_per_seq=8)
    for uid in range(4):
        s.submit(_req(uid))
    a, b = s.admit_next(), s.admit_next()
    assert {a.slot, b.slot} == {0, 1}
    assert s.admit_next() is None              # both slots busy
    s.finish(a)
    c = s.admit_next()
    assert c.slot == a.slot                    # freed slot reused
    assert s.cache.seq_lens[c.slot] == len(c.request.prompt)
    s.finish(b), s.finish(c)
    d = s.admit_next()
    assert d is not None and not s.queue
    s.finish(d)
    assert s.allocator.used_count == 0         # everything returned


def test_scheduler_page_growth_and_preemption():
    # admission leaves exactly one page of headroom (anti-thrash rule); once
    # growth burns it, growing the older sequence must preempt the newer
    s = Scheduler(num_slots=2, num_pages=8, page_size=4, max_pages_per_seq=8)
    s.submit(_req(0, plen=8, gen=16))          # 3 pages
    s.submit(_req(1, plen=8, gen=16))          # 3 pages + 1 headroom
    s0, s1 = s.admit_next(), s.admit_next()
    assert s0 is not None and s1 is not None
    assert s.allocator.free_count == 1
    s.cache.seq_lens[s0.slot] = 12             # slot 0 full: next token -> page 4
    assert s.ensure_capacity() == []           # headroom page absorbs growth
    assert s.cache.allocated_pages(s0.slot) == 4
    s.cache.seq_lens[s0.slot] = 16             # full again: next -> page 5
    preempted = s.ensure_capacity()
    assert [p.request.uid for p in preempted] == [1]
    assert s.queue[0].uid == 1                 # requeued at the front
    assert s.cache.allocated_pages(s0.slot) == 5


def test_scheduler_headroom_blocks_zero_slack_admission():
    """With sequences already running, admission must leave >= 1 free page —
    a zero-slack admit would be the first preemption victim the moment any
    neighbour grows (admit/preempt thrash)."""
    s = Scheduler(num_slots=2, num_pages=7, page_size=4, max_pages_per_seq=8)
    s.submit(_req(0, plen=8, gen=16))          # 3 pages, nothing running: ok
    s.submit(_req(1, plen=8, gen=16))          # would leave 0 free: refused
    s0 = s.admit_next()
    assert s0 is not None
    assert s.admit_next() is None
    assert s.allocator.free_count == 3         # refused admit took nothing
    s.finish(s0)
    assert s.admit_next().request.uid == 1     # pool empty again: admitted


def test_scheduler_rejects_oversized_request_and_keeps_serving():
    """A context that can never fit in max_pages_per_seq must fail that one
    request (surfaced via take_rejected), not raise and kill the engine."""
    s = Scheduler(num_slots=2, num_pages=64, page_size=4, max_pages_per_seq=4)
    s.submit(_req(0, plen=8))
    s.submit(_req(1, plen=40))                 # 11 pages > 4: impossible
    s.submit(_req(2, plen=8))
    a = s.admit_next()
    assert a is not None and a.request.uid == 0
    b = s.admit_next()                         # skips over the doomed request
    assert b is not None and b.request.uid == 2
    assert [r.uid for r in s.take_rejected()] == [1]
    assert s.take_rejected() == []             # drained
    s.finish(a), s.finish(b)
    assert s.allocator.used_count == 0


# ------------------------------------------------------------------ e2e parity ---

def _fp32_model(name):
    arch = smoke_config(name)
    arch = dataclasses.replace(arch, dtype="float32", param_dtype="float32")
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    return arch, model, params


def _static_greedy(model, params, prompts, gens):
    """Per-request static decode (batch 1): the reference token stream."""
    out = []
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    for i, prompt in enumerate(prompts):
        plen, glen = len(prompt), gens[i]
        caches = model.init_caches(None, 1, plen + glen)
        logits, caches = prefill(params, caches,
                                 {"tokens": jnp.asarray([prompt])})
        tok = jnp.argmax(logits[:, -1], axis=-1)
        ids = [int(tok[0])]
        for s in range(glen - 1):
            logits, caches = decode(
                params, caches,
                {"tokens": tok[:, None],
                 "positions": jnp.full((1,), plen + s, jnp.int32)})
            tok = jnp.argmax(logits[:, -1], axis=-1)
            ids.append(int(tok[0]))
        out.append(ids)
    return out


@pytest.mark.parametrize("name", ["llama3.2-3b", "qwen2-vl-2b"])
def test_continuous_matches_static_greedy(name):
    arch, model, params = _fp32_model(name)
    rng = np.random.default_rng(3)
    prompts = [list(map(int, rng.integers(5, arch.vocab_size, rng.integers(6, 14))))
               for _ in range(4)]
    gens = [6, 11, 4, 9]                       # ragged generation lengths
    ref = _static_greedy(model, params, prompts, gens)

    engine = ContinuousEngine(model, params, num_slots=4, num_pages=48,
                              page_size=8, max_seq_len=64)
    res = engine.run([Request(uid=i, prompt=prompts[i], max_new_tokens=gens[i])
                      for i in range(4)])
    for i in range(4):
        assert res[i]["tokens"] == ref[i], f"request {i} diverged"
    assert engine.live_kv_tokens == 0          # all pages recycled


def test_continuous_matches_static_under_recycling_and_preemption():
    """slots < requests and a page pool too small for all of them: recycling
    and recompute-preemption must not change a single greedy token.
    (prefix_cache off so the drained pool is exactly empty — the index would
    deliberately retain pages.)"""
    arch, model, params = _fp32_model("llama3.2-3b")
    rng = np.random.default_rng(7)
    prompts = [list(map(int, rng.integers(5, arch.vocab_size, 12)))
               for _ in range(5)]
    gens = [4, 16, 7, 12, 9]
    ref = _static_greedy(model, params, prompts, gens)

    engine = ContinuousEngine(model, params, num_slots=2, num_pages=10,
                              page_size=4, max_seq_len=32,
                              prefix_cache=False)
    res = engine.run([Request(uid=i, prompt=prompts[i], max_new_tokens=gens[i])
                      for i in range(5)])
    for i in range(5):
        assert res[i]["tokens"] == ref[i], f"request {i} diverged"
    assert engine.prefills > 5                 # preemption actually happened
    assert engine.scheduler.allocator.used_count == 0


def test_overlong_prompt_gets_error_result_not_engine_death():
    """Regression: one request whose context exceeds max_pages_per_seq used
    to raise out of admit_next mid-trace, killing every in-flight request.
    It must come back as an error result while the rest serve normally."""
    arch, model, params = _fp32_model("llama3.2-3b")
    rng = np.random.default_rng(11)
    ok_prompts = [list(map(int, rng.integers(5, arch.vocab_size, 10)))
                  for _ in range(2)]
    ref = _static_greedy(model, params, ok_prompts, [5, 7])
    engine = ContinuousEngine(model, params, num_slots=2, num_pages=32,
                              page_size=8, max_seq_len=32)   # 4 pages/seq
    reqs = [Request(uid=0, prompt=ok_prompts[0], max_new_tokens=5),
            Request(uid=1, prompt=list(range(5, 5 + 40)),    # needs 6 pages
                    max_new_tokens=5),
            Request(uid=2, prompt=ok_prompts[1], max_new_tokens=7)]
    res = engine.run(reqs)
    assert "error" in res[1] and res[1]["tokens"] == []
    assert res[0]["tokens"] == ref[0]
    assert res[2]["tokens"] == ref[1]


def test_generation_outgrowing_max_seq_len_truncates_not_crashes():
    """Regression: a prompt that fits but whose max_new_tokens would outgrow
    the page table used to die mid-trace in append_page ('page table full'),
    discarding every in-flight request. It must truncate at cache capacity
    and the other requests must be untouched."""
    arch, model, params = _fp32_model("llama3.2-3b")
    rng = np.random.default_rng(19)
    big = list(map(int, rng.integers(5, arch.vocab_size, 16)))
    ok = list(map(int, rng.integers(5, arch.vocab_size, 8)))
    ref_ok = _static_greedy(model, params, [ok], [5])[0]
    engine = ContinuousEngine(model, params, num_slots=2, num_pages=32,
                              page_size=8, max_seq_len=32)   # 32-token cap
    res = engine.run([Request(uid=0, prompt=big, max_new_tokens=40),
                      Request(uid=1, prompt=ok, max_new_tokens=5)])
    assert len(res[0]["tokens"]) == 32 - 16        # truncated at capacity
    assert res[1]["tokens"] == ref_ok
    assert engine.live_kv_tokens == 0


def test_admission_headroom_bounds_reprefills():
    """Regression for admit/preempt thrash: with a pool where the second
    request fits only with zero slack, the old scheduler admitted it, paid
    its prefill, then chose it as the preemption victim as soon as the first
    sequence grew — re-prefilling on a loop. With admission headroom, total
    prefill completions stay at (admissions + genuine preemptions)."""
    arch, model, params = _fp32_model("llama3.2-3b")
    rng = np.random.default_rng(13)
    prompts = [list(map(int, rng.integers(5, arch.vocab_size, 8)))
               for _ in range(2)]
    gens = [16, 16]
    ref = _static_greedy(model, params, prompts, gens)
    engine = ContinuousEngine(model, params, num_slots=2, num_pages=10,
                              page_size=4, max_seq_len=32,
                              prefix_cache=False)
    res = engine.run([Request(uid=i, prompt=prompts[i], max_new_tokens=gens[i])
                      for i in range(2)])
    for i in range(2):
        assert res[i]["tokens"] == ref[i], f"request {i} diverged"
    # 2 admissions + at most one growth-driven preemption/re-admission; the
    # thrash regression showed up as a prefill per crossed page boundary
    assert engine.prefills <= 3


def test_preempted_midprefill_sequence_readmits_instead_of_stalling():
    """Regression: preempting a sequence that is mid-prefill left its stale
    entry gating admission (the prefix-cache serialized-admission gate); if
    the other sequence finished on that same iteration the engine saw
    {nothing running, non-empty queue} and raised 'queue stalled' for a
    perfectly admittable request. Forces exactly that interleaving: the
    victim must simply be re-admitted and complete."""
    arch, model, params = _fp32_model("llama3.2-3b")
    rng = np.random.default_rng(17)
    # timing: uid0 (8-tok prompt, 4-tok chunks) prefills over iterations 1-2
    # and decodes from iteration 2; uid1 is admitted at iteration 3 and is
    # mid-prefill there, which is exactly when uid0's final decode runs
    prompts = [list(map(int, rng.integers(5, arch.vocab_size, 8))),
               list(map(int, rng.integers(5, arch.vocab_size, 12)))]
    gens = [3, 3]
    ref = _static_greedy(model, params, prompts, gens)
    engine = ContinuousEngine(model, params, num_slots=2, num_pages=32,
                              page_size=4, max_seq_len=48,
                              prefix_cache=True, prefill_chunk=4)
    sched = engine.scheduler
    orig = sched.ensure_capacity
    forced = []

    def force_preempt_midprefill():
        out = orig()
        victim = next((s for s in sched.running.values()
                       if s.prefilled < s.prefill_target), None)
        if not forced and victim is not None and len(sched.running) > 1:
            sched._preempt(victim)      # simulated pool pressure
            out.append(victim)
            forced.append(victim.request.uid)
        return out

    sched.ensure_capacity = force_preempt_midprefill
    res = engine.run([Request(uid=i, prompt=prompts[i],
                              max_new_tokens=gens[i]) for i in range(2)])
    assert forced == [1], "scenario must actually fire"
    for i in range(2):
        assert res[i]["tokens"] == ref[i], f"request {i} diverged"


def test_eos_stops_generation_early():
    arch, model, params = _fp32_model("llama3.2-3b")
    prompt = list(range(5, 15))
    ref = _static_greedy(model, params, [prompt], [12])[0]
    eos = ref[3]                               # force an early stop
    stop = ref.index(eos) + 1                  # first occurrence wins
    engine = ContinuousEngine(model, params, num_slots=2, num_pages=32,
                              page_size=8, max_seq_len=64)
    res = engine.run([Request(uid=0, prompt=prompt, max_new_tokens=12,
                              eos_id=eos)])
    assert res[0]["tokens"] == ref[:stop]
    assert engine.live_kv_tokens == 0


# ------------------------------------------------------- decode-step writes ---

def _unrolled(tree):
    """A scanned stack (params or decode state, leaves [periods, ...]) as
    the unrolled ``period_z`` dicts, whose layers hold single pools; a
    leading dense stack is unrolled already."""
    if "main" in tree:
        return {"lead": tree["lead"], "main": _unrolled(tree["main"])}
    n = jax.tree.leaves(tree)[0].shape[0]
    return {f"period_{z}": jax.tree.map(lambda a, z=z: a[z], tree)
            for z in range(n)}


def _restacked(tree):
    if "main" in tree:
        return {"lead": tree["lead"], "main": _restacked(tree["main"])}
    periods = [tree[f"period_{z}"] for z in range(len(tree))]
    return jax.tree.map(lambda *a: jnp.stack(a), *periods)


@pytest.mark.parametrize("name,layers", [("internlm2-1.8b", 3),
                                         ("deepseek-moe-16b-ep4", 3),
                                         ("jamba-v0.1-52b", 16)])
def test_decode_step_writes_only_its_rows(name, layers):
    """One decode step through a scanned stack, which carries the stacked
    state and hands attention the stack and the period index: in every
    layer's pool exactly the step's B rows change (an inactive slot's at
    the null page), in mamba state exactly the active slots' rows, and the
    hidden state, tokens and new state equal the unrolled stack's, whose
    layers each read and write a pool of their own."""
    from repro.models import transformer as tf
    arch = dataclasses.replace(smoke_config(name), num_layers=layers,
                               dtype="float32", param_dtype="float32")
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    stacked = params["blocks"]
    pools = tf.init_serving_state(arch, 12, 4, 3, jnp.float32)
    leaves, treedef = jax.tree.flatten(pools)
    keys = jax.random.split(jax.random.key(1), len(leaves))
    pools = jax.tree.unflatten(treedef, [
        jax.random.normal(k, l.shape, l.dtype) for k, l in zip(keys, leaves)])
    table = jnp.asarray([[3, 7, 1], [0, 0, 0], [5, 2, 9]], jnp.int32)
    lens = jnp.asarray([5, 0, 8], jnp.int32)      # slot 1 inactive
    x = model._embed(params, jnp.asarray([[11], [0], [42]], jnp.int32))

    step = jax.jit(functools.partial(tf.paged_decode_stack, arch))
    got_x, got, _ = step(stacked, pools, x, table, lens)
    un_x, un, _ = step(_unrolled(stacked), _unrolled(pools), x, table, lens)
    np.testing.assert_allclose(got_x, un_x, rtol=0, atol=1e-5)
    np.testing.assert_array_equal(
        jnp.argmax(model._logits(params, got_x), -1),
        jnp.argmax(model._logits(params, un_x), -1))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
                 got, _restacked(un))

    pids = np.asarray(table)[np.arange(3), np.asarray(lens) // 4]
    offs = np.asarray(lens) % 4
    active = np.asarray(lens) > 0
    kinds = set()
    for path, old in jax.tree_util.tree_leaves_with_path(pools):
        new = functools.reduce(lambda t, k: t[k.key], path, got)
        kind = path[-1].key
        kinds.add(kind)
        if kind in ("k", "v"):
            # rows [(L,) P, page]: the step's (page, offset) of every layer
            changed = np.asarray(jnp.any(new != old, axis=(-2, -1)))
            want = np.zeros(old.shape[:-2], bool)
            want[..., pids, offs] = True
            np.testing.assert_array_equal(changed, want, str(path))
        else:
            # slot state [L, S, ...]: the active slots of every layer
            changed = np.asarray(jnp.any(
                new != old, axis=tuple(range(2, old.ndim))))
            np.testing.assert_array_equal(
                changed, np.broadcast_to(active, changed.shape), str(path))
    assert {"k", "v"} <= kinds
