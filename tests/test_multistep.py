"""Multi-step compiled decode loop: N iterations per host dispatch.

The acceptance surface of the multi-step loop is *invisibility*: at any
horizon N the engine must emit streams bit-identical to ``decode_steps=1``
for every servable family, truncate exactly at a mid-loop EOS (iterations
k+1..N of a dispatch must never leak into a stream), replay token-identically
when a preemption lands between multi-step dispatches, and keep the
sanitizer's allocator invariants (pages freed exactly once). Parity runs in
fp32, like the cross-engine sampled-parity tests: bf16's reassociated
summation flips near-tied draws of random-init smoke models, which is
rounding noise, not loop divergence.

tp=2 parity runs in a subprocess with forced host devices (the pattern
``test_sharding.py`` established), so it executes in the plain tier-1 run
too; the ``tier1-multidevice`` CI job additionally runs this whole file
in-process under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import dataclasses
import subprocess
import sys
from functools import lru_cache
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.analysis.recompile import FAMILY_ARCHS, audit_family
from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import ContinuousEngine, Request
from repro.serving.sampling import SamplingParams

ROOT = Path(__file__).resolve().parents[1]


@lru_cache(maxsize=None)
def _fp32_model(name):
    arch = smoke_config(name)
    arch = dataclasses.replace(arch, dtype="float32", param_dtype="float32")
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    return arch, model, params


def _requests(arch, n=4, seed=7):
    """Mixed greedy / sampled / filtered traffic with ragged lengths."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        prompt = list(map(int, rng.integers(5, arch.vocab_size,
                                            int(rng.integers(6, 18)))))
        sp = (SamplingParams(),
              SamplingParams(temperature=0.8, seed=100 + i),
              SamplingParams(temperature=0.9, top_k=8, top_p=0.9,
                             seed=200 + i))[i % 3]
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(4, 9)),
                            sampling=sp))
    return reqs


def _serve(model, params, reqs, *, decode_steps, **kw):
    """One engine run with the sanitizer ON (every completion re-checks the
    allocator conservation + refcount invariants, so a page freed twice by
    the multi-step resync fails here, not in a later test)."""
    defaults = dict(num_slots=3, num_pages=64, page_size=4, max_seq_len=64,
                    prefix_cache=False, sanitize=True)
    defaults.update(kw)
    engine = ContinuousEngine(model, params, decode_steps=decode_steps,
                              **defaults)
    res = engine.run(list(reqs))
    return engine, {uid: r["tokens"] for uid, r in res.items()}


# ------------------------------------------------------------------- parity ----

@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_multistep_parity_all_families(family):
    """Streams bit-identical between decode_steps=1 and N>1 for every
    servable family — and the loop must actually amortize dispatches
    (decode_dispatches < decode steps), or it is an expensive no-op."""
    arch, model, params = _fp32_model(FAMILY_ARCHS[family])
    reqs = _requests(arch)
    e1, ref = _serve(model, params, reqs, decode_steps=1)
    assert e1.decode_dispatches == e1.steps      # N=1: one step per dispatch
    for n in ((4, 16) if family == "dense" else (4,)):
        en, toks = _serve(model, params, reqs, decode_steps=n)
        assert toks == ref, f"{family} diverged at decode_steps={n}"
        assert en.decode_dispatches < en.steps, \
            f"{family} N={n}: loop never ran more than one iteration"


# ------------------------------------------------------------- EOS mid-loop ----

def test_eos_mid_loop_truncates_and_frees_once():
    """A slot hitting EOS on loop iteration k < N: iterations k+1..N must
    not be visible in its stream, the dispatch must report the eos exit,
    and (sanitizer on) its pages are freed exactly once."""
    arch, model, params = _fp32_model("llama3.2-3b")
    rng = np.random.default_rng(23)
    prompt = list(map(int, rng.integers(5, arch.vocab_size, 9)))
    # sampled, not greedy: the smoke model's greedy stream collapses to one
    # repeated token, which never yields a usable first-occurrence EOS id
    base = Request(uid=0, prompt=prompt, max_new_tokens=24,
                   sampling=SamplingParams(temperature=1.0, seed=23))
    _, ref = _serve(model, params, [base], decode_steps=16)
    stream = ref[0]
    # pick an EOS id whose FIRST occurrence is a decode token (index >= 2:
    # index 0 is emitted by the final prefill chunk, not the loop) that
    # lands strictly inside the 16-step horizon
    eos, k = next(((t, i) for i, t in enumerate(stream)
                   if 2 <= i <= 14 and stream.index(t) == i), (None, None))
    assert eos is not None, f"no mid-horizon token to use as EOS: {stream}"
    e, toks = _serve(model, params,
                     [dataclasses.replace(base, eos_id=eos)],
                     decode_steps=16)
    assert toks[0] == stream[:k + 1], \
        "EOS truncation diverged from the unbounded stream"
    assert toks[0][-1] == eos and eos not in toks[0][:-1]
    assert e.decode_exits["eos"] == 1
    assert e.decode_dispatches == 1 and e.steps == k, \
        "EOS within the first horizon must cost exactly one dispatch"
    # drained engine holds nothing: pages freed exactly once, all returned
    assert e.pages_in_use == 0


# ----------------------------------------------- preemption between dispatches -

def _forced_preempt_engine(model, params, *, uid, when, **kw):
    """Engine whose scheduler force-preempts request ``uid`` once, the first
    time ``when(seq)`` holds (simulated pool pressure, deterministic) —
    the pattern ``test_sampling.py`` established."""
    engine = ContinuousEngine(model, params, **kw)
    sched = engine.scheduler
    orig = sched.ensure_capacity
    fired = []

    def forced():
        out = orig()
        victim = next((s for s in sched.running.values()
                       if s.request.uid == uid), None)
        if not fired and victim is not None and not victim.done \
                and len(sched.running) > 1 and when(victim):
            sched._preempt(victim)
            out.append(victim)
            fired.append(victim.request.uid)
        return out

    sched.ensure_capacity = forced
    return engine, fired


def test_preemption_between_multistep_dispatches_replays_identically():
    """A forced preemption landing between multi-step dispatches (the victim
    already holds several loop-emitted tokens) must replay token-identically
    vs an unpreempted decode_steps=1 run: forced replay re-derives every
    PRNG key from the stream position, so the horizon is token-invisible."""
    arch, model, params = _fp32_model("llama3.2-3b")
    reqs = _requests(arch, seed=29)
    reqs = [dataclasses.replace(r, max_new_tokens=max(r.max_new_tokens, 8))
            for r in reqs]
    _, ref = _serve(model, params, reqs, decode_steps=1)
    kw = dict(num_slots=3, num_pages=64, page_size=4, max_seq_len=64,
              prefix_cache=False, sanitize=True, decode_steps=4)
    engine, fired = _forced_preempt_engine(
        model, params, uid=1, when=lambda seq: len(seq.generated) >= 3, **kw)
    res = engine.run(list(reqs))
    assert fired == [1], "forced preemption must actually fire"
    assert {uid: r["tokens"] for uid, r in res.items()} == ref, \
        "preempted+resumed multi-step stream diverged from N=1"


# -------------------------------------------------------- dispatch accounting --

def test_dispatch_accounting_and_exit_reasons():
    """Host dispatches per decode-emitted token fall under the bench's
    1.1/N bound on plain traffic, and the exit-reason counters record why
    each dispatch returned (budget exits for every finishing slot, horizon
    exits for full-length dispatches with no event)."""
    arch, model, params = _fp32_model("llama3.2-3b")
    rng = np.random.default_rng(31)
    reqs = [Request(uid=i,
                    prompt=list(map(int, rng.integers(5, arch.vocab_size,
                                                      10))),
                    max_new_tokens=12)
            for i in range(4)]
    e1, ref = _serve(model, params, reqs, decode_steps=1, num_slots=4)
    e4, toks = _serve(model, params, reqs, decode_steps=4, num_slots=4)
    assert toks == ref
    # each request's first token comes from its final prefill chunk
    decode_tokens = sum(len(v) for v in toks.values()) - len(reqs)
    assert e4.decode_dispatches / decode_tokens < 1.1 / 4
    assert e4.decode_exits["token_budget"] >= 1   # every request ends on it
    assert e4.decode_exits["horizon"] >= 1        # 12 tokens span >1 horizon
    assert e4.decode_exits["eos"] == 0
    assert e1.decode_exits == {"eos": 0, "token_budget": 0,
                               "page_budget": 0, "horizon": 0}, \
        "N=1 keeps the single-step path: no loop, no exit accounting"


# ------------------------------------------------------------- audit closure ---

def test_recompile_audit_covers_multistep_variants():
    """decode_steps=4 re-keys every decode variant on the horizon (key arity
    4 — the trailing element is N) and the jit cache still closes: steps
    2..N of the audit trace add zero traces."""
    report = audit_family("dense", decode_steps=4)
    decode_keys = [k for k in report.variants if k and k[0] == "decode"]
    assert decode_keys, "audit trace exercised no decode variant"
    assert all(len(k) == 4 and k[-1] == 4 for k in decode_keys), decode_keys
    # prefill variants must not be re-keyed by the decode horizon: their key
    # set is identical to what the same trace produces at N=1
    ref = audit_family("dense", decode_steps=1)
    prefill = lambda r: {k for k in r.variants if k and k[0] == "prefill"}
    assert prefill(report) == prefill(ref), \
        (prefill(report), prefill(ref))


# ------------------------------------------------------------------ tp parity --

def _run_subprocess(body: str):
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n" + body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, cwd=ROOT, timeout=500,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    return r.stdout


def test_tp2_multistep_parity():
    """Mixed traffic token-identical between (tp=1, N=1) and (tp=2, N∈{4,16}):
    the while_loop carries replicated control state over the sharded pools,
    so the horizon composes with head-sharded TP without divergence."""
    out = _run_subprocess(r"""
import dataclasses
import jax, numpy as np
from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import ContinuousEngine, Request
from repro.serving.sampling import SamplingParams

arch = dataclasses.replace(smoke_config("llama3.2-3b"), num_kv_heads=4,
                           dtype="float32", param_dtype="float32")
model = build_model(arch)
params = model.init(jax.random.key(0))
rng = np.random.default_rng(11)
prompts = [list(map(int, rng.integers(5, arch.vocab_size, 10)))
           for _ in range(4)]

def serve(tp, n):
    reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=8,
                    sampling=(SamplingParams(temperature=0.8, top_k=8,
                                             seed=50 + i)
                              if i % 2 else SamplingParams()))
            for i in range(4)]
    engine = ContinuousEngine(model, params, num_slots=3, num_pages=48,
                              page_size=4, max_seq_len=48,
                              prefix_cache=False, tp=tp, decode_steps=n)
    res = engine.run(reqs)
    return {uid: r["tokens"] for uid, r in res.items()}

ref = serve(1, 1)
assert serve(2, 4) == ref, "tp=2 N=4 diverged"
assert serve(2, 16) == ref, "tp=2 N=16 diverged"
print("TP-MULTISTEP-OK")
""")
    assert "TP-MULTISTEP-OK" in out
