"""Fused decode residual stream + streaming LM-head epilogue.

Three layers of acceptance:

1. Epilogue correctness — ``kernels.fused_lm_head`` defines the canonical
   inverse-CDF draw ONCE (``ref.head_epilogue`` on materialized logits);
   the vocab-streaming jnp path and the Pallas kernel (interpret mode on
   CPU) must reproduce it BIT-for-bit on the edge cases that historically
   break samplers: fully-masked (all ``-inf``) rows, rows holding ``-inf``
   entries, ``top_p == 1.0``, ``top_k >= V``, and kth-value ties that
   straddle a vocab-tile boundary.

2. Memory shape — the streaming path's compiled HLO must never allocate an
   ``f32 [S, V]`` logits buffer (that buffer's absence IS the optimization);
   the materializing oracle is the positive control proving the assertion
   can fail. This is asserted on the STREAMING implementation's graph: on
   CPU the engine serves the materializing head (``engine._head``), so the
   engine's own CPU HLO is out of scope here by design
   (``tests/test_tpu_compile.py`` compiles the engine's step for a v5e).

3. Engine invisibility — the engine's one decode path (residual+norm-fused
   period bodies, then ``_head``) emits the float32 static engine's token
   streams for every servable family, the same streams at decode horizon
   N=1 and N=4, across forced-preemption replay, and under tp=2, where a
   vocab shard off the reduction tile serves through the materializing
   head — plus the construction-time refusals of post-norm stacks and MLM
   heads.
"""
import dataclasses
import subprocess
import sys
from functools import lru_cache
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep (requirements-dev.txt)
    given = settings = st = None

from repro.analysis.recompile import FAMILY_ARCHS
from repro.configs import smoke_config
from repro.kernels.fused_lm_head import kernel as head_kernel
from repro.kernels.fused_lm_head import ops as head_ops
from repro.kernels.fused_lm_head import ref as head_ref
from repro.models import build_model
from repro.serving import ContinuousEngine, Request, sample_tokens
from repro.serving.sampling import SamplingParams

ROOT = Path(__file__).resolve().parents[1]


# --------------------------------------------------- epilogue: pinned edges ----

def _epilogue_ref(logits, rs, temps, tk, tp):
    return jax.jit(lambda *a: head_ref.head_epilogue(
        *a, sampled=True, filtered=True))(logits, rs, temps, tk, tp)


def test_epilogue_fully_masked_row_draws_token_zero():
    """All-(-inf) row: zero total mass, the prefix walk never fires, and the
    canonical draw's deterministic fallback is token 0 (ref docstring step
    6); the finite probe must report the row bad."""
    v = 256
    logits = jnp.stack([
        jnp.full((v,), -jnp.inf, jnp.float32),            # fully masked
        jnp.linspace(-1, 1, v, dtype=jnp.float32),        # healthy control
    ])
    rs = jnp.asarray([0.7, 0.3], jnp.float32)
    temps = jnp.asarray([1.0, 1.0], jnp.float32)
    tk = jnp.asarray([0, 0], jnp.int32)
    tp = jnp.asarray([1.0, 1.0], jnp.float32)
    tok, ok = _epilogue_ref(logits, rs, temps, tk, tp)
    assert int(tok[0]) == 0
    assert not bool(ok[0]) and bool(ok[1])


def test_epilogue_neg_inf_entries_carry_zero_mass():
    """Rows holding -inf entries: the probe flags them, but the draw is
    still well-defined — masked entries carry exp(-inf) = 0 mass so no
    uniform can ever land on one."""
    v = 256
    rng = np.random.default_rng(3)
    base = rng.normal(size=(4, v)).astype(np.float32)
    masked = rng.random(size=(4, v)) < 0.5
    masked[:, 7] = False                       # keep at least one live lane
    base[masked] = -np.inf
    logits = jnp.asarray(base)
    rs = jnp.asarray(rng.random(4), jnp.float32)
    temps = jnp.full((4,), 0.9, jnp.float32)
    tok, ok = _epilogue_ref(logits, rs, temps,
                            jnp.zeros((4,), jnp.int32),
                            jnp.ones((4,), jnp.float32))
    assert not bool(ok.any())
    for r in range(4):
        assert not masked[r, int(tok[r])], f"row {r} drew a masked lane"


def test_epilogue_top_p_one_and_top_k_ge_v_filter_nothing():
    """top_p == 1.0 and top_k >= V are the no-op corners of the filter: the
    filtered draw must equal the unfiltered draw bitwise."""
    v = 384
    logits = jax.random.normal(jax.random.key(5), (3, v), jnp.float32)
    rs = jnp.asarray([0.11, 0.52, 0.93], jnp.float32)
    temps = jnp.asarray([0.7, 1.0, 1.3], jnp.float32)
    tok_f, ok_f = _epilogue_ref(
        logits, rs, temps,
        jnp.asarray([v, v + 7, 0], jnp.int32),          # >= V or disabled
        jnp.ones((3,), jnp.float32))                    # exactly 1.0
    tok_u, ok_u = jax.jit(lambda *a: head_ref.head_epilogue(
        *a, sampled=True, filtered=False))(
        logits, rs, temps, jnp.zeros((3,), jnp.int32),
        jnp.ones((3,), jnp.float32))
    assert jnp.array_equal(tok_f, tok_u) and jnp.array_equal(ok_f, ok_u)


# ---------------------------------------- epilogue: three-way implementations --

def _threeway(x, w, rs, temps, tk, tp, *, sampled=True, filtered=True,
              softcap=None):
    """(oracle, streaming-jnp, Pallas-interpret) under jit — every
    comparison in this file is jit-vs-jit (eager CPU constant-folds float
    reductions differently, a known 1-ulp hazard unrelated to the fusion)."""
    def oracle(x, w, rs, temps, tk, tp):
        lg = (x @ w.astype(x.dtype)).astype(jnp.float32)
        if softcap:
            lg = softcap * jnp.tanh(lg / softcap)
        return head_ref.head_epilogue(lg, rs, temps, tk, tp,
                                      sampled=sampled, filtered=filtered)

    def stream(x, w, rs, temps, tk, tp):
        return head_ops._head_tokens_jnp(x, w, rs, temps, tk, tp,
                                         sampled=sampled, filtered=filtered,
                                         softcap=softcap, axis_name=None,
                                         tp=1)

    def pallas(x, w, rs, temps, tk, tp):
        return head_kernel.head_tokens(x, w, rs, temps, tk, tp,
                                       sampled=sampled, filtered=filtered,
                                       softcap=softcap, interpret=True)

    args = (x, w, rs, temps, tk, tp)
    return (jax.jit(oracle)(*args), jax.jit(stream)(*args),
            jax.jit(pallas)(*args))


def _assert_threeway_equal(x, w, rs, temps, tk, tp, **kw):
    (t0, k0), (t1, k1), (t2, k2) = _threeway(x, w, rs, temps, tk, tp, **kw)
    assert jnp.array_equal(t0, t1), "streaming tokens diverged from oracle"
    assert jnp.array_equal(t0, t2), "pallas tokens diverged from oracle"
    assert jnp.array_equal(k0, k1) and jnp.array_equal(k0, k2), \
        "finite probes diverged"
    return t0


def test_threeway_kth_value_ties_across_tile_boundary():
    """A run of identical logits straddling both the RED_TILE (128) and the
    GEMM-tile boundary, with top_k cutting inside the run: the count-based
    bisection keeps ALL tied lanes (>= kth survives — same contract as the
    fused_sampling filter), and all three implementations must agree on
    which lane the draw lands on. V=640 streams five 128-wide GEMM tiles,
    so the tie at 126..130 crosses a real tile edge. Identity weights make
    the GEMM inject the crafted logits exactly."""
    v = 640
    assert head_ref.gemm_tile(v) == 128
    rng = np.random.default_rng(9)
    base = rng.normal(scale=0.1, size=(6, v)).astype(np.float32)
    base[:, 126:131] = 3.0                     # 5-way tie across the edge
    base[:, 255:258] = 2.5                     # second tie at the next edge
    x = jnp.asarray(base)
    w = jnp.eye(v, dtype=jnp.float32)
    rs = jnp.asarray(rng.random(6), jnp.float32)
    temps = jnp.asarray([1.0, 0.8, 1.0, 0.0, 1.2, 1.0], jnp.float32)
    tk = jnp.asarray([3, 2, 6, 4, 1, 7], jnp.int32)    # cuts inside the ties
    tp = jnp.asarray([1.0, 0.95, 0.9, 1.0, 1.0, 0.8], jnp.float32)
    tok = _assert_threeway_equal(x, w, rs, temps, tk, tp)
    # top_k=1 with a 5-way tie keeps the whole tie class; the greedy row
    # (temps == 0) must take the FIRST tied lane
    assert int(tok[3]) == 126


def test_threeway_pinned_param_corners():
    """Pinned corners through real (non-identity) weights: greedy rows mixed
    with sampled, top_p exactly 1.0, top_k >= V, top_k == 1, bf16 hidden,
    and a softcap — all three implementations bit-agree."""
    s, d, v = 5, 64, 384
    x = jax.random.normal(jax.random.key(0), (s, d), jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(1), (d, v), jnp.float32)
         * 0.1).astype(jnp.bfloat16)
    rs = jnp.asarray([0.01, 0.5, 0.99, 0.33, 0.66], jnp.float32)
    temps = jnp.asarray([0.0, 1.0, 0.7, 1.5, 1.0], jnp.float32)
    tk = jnp.asarray([0, v + 3, 1, 8, 0], jnp.int32)
    tp = jnp.asarray([1.0, 1.0, 0.9, 0.5, 1.0], jnp.float32)
    _assert_threeway_equal(x, w, rs, temps, tk, tp)
    _assert_threeway_equal(x, w, rs, temps, tk, tp, softcap=30.0)
    _assert_threeway_equal(x, w, rs, temps, tk, tp, sampled=False)
    _assert_threeway_equal(x, w, rs, temps, tk, tp, filtered=False)


if st is not None:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 1000),
           v=st.sampled_from([256, 384, 512, 640]),
           dtype=st.sampled_from(["float32", "bfloat16"]))
    def test_threeway_property_sweep(seed, v, dtype):
        s, d = 4, 32
        ks = jax.random.split(jax.random.key(seed), 6)
        dt = jnp.dtype(dtype)
        x = jax.random.normal(ks[0], (s, d), dt)
        w = (jax.random.normal(ks[1], (d, v), jnp.float32) * 0.2).astype(dt)
        rs = jax.random.uniform(ks[2], (s,), jnp.float32)
        temps = jax.random.uniform(ks[3], (s,), jnp.float32, 0.0, 1.5)
        tk = jax.random.randint(ks[4], (s,), 0, v + 2)
        tp = jax.random.uniform(ks[5], (s,), jnp.float32, 0.1, 1.0)
        _assert_threeway_equal(x, w, rs, temps, tk, tp)
else:
    def test_threeway_property_sweep():
        pytest.importorskip("hypothesis")


# ------------------------------------------------- no [S, V] buffer in HLO ----

def test_streaming_hlo_never_holds_logits_row():
    """The whole point of the streaming epilogue: its optimized HLO holds no
    f32 [S, V] tensor. The materializing oracle is the positive control —
    the same shape string MUST appear there, proving the probe detects what
    it claims to rule out. (S=4 is chosen so the [S, V] shape string cannot
    collide with the [D, V] weight, D=64.)"""
    s, d, v = 4, 64, 1024
    x = jax.random.normal(jax.random.key(0), (s, d), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (d, v), jnp.float32)
    rs = jnp.full((s,), 0.5, jnp.float32)
    temps = jnp.full((s,), 1.0, jnp.float32)
    tk = jnp.full((s,), 8, jnp.int32)
    tp = jnp.full((s,), 0.9, jnp.float32)
    needle = f"f32[{s},{v}]"

    def stream(x, w, rs, temps, tk, tp):
        return head_ops._head_tokens_jnp(x, w, rs, temps, tk, tp,
                                         sampled=True, filtered=True,
                                         softcap=None, axis_name=None, tp=1)

    def materialize(x, w, rs, temps, tk, tp):
        lg = (x @ w).astype(jnp.float32)
        return head_ref.head_epilogue(lg, rs, temps, tk, tp,
                                      sampled=True, filtered=True)

    args = (x, w, rs, temps, tk, tp)
    hlo_stream = jax.jit(stream).lower(*args).compile().as_text()
    hlo_mat = jax.jit(materialize).lower(*args).compile().as_text()
    assert needle in hlo_mat, \
        "positive control lost its logits buffer — probe is meaningless"
    assert needle not in hlo_stream, \
        f"streaming epilogue materialized a {needle} logits buffer"
    # and the two graphs still agree on the tokens they emit
    t_s, k_s = jax.jit(stream)(*args)
    t_m, k_m = jax.jit(materialize)(*args)
    assert jnp.array_equal(t_s, t_m) and jnp.array_equal(k_s, k_m)


# ------------------------------------------------------- engine bit-parity ----

@lru_cache(maxsize=None)
def _smoke_model(name):
    arch = smoke_config(name)
    model = build_model(arch)
    return arch, model, model.init(jax.random.key(0))


@lru_cache(maxsize=None)
def _fp32_model(name):
    """The float32 smoke model the static engine is compared against. MoE
    capacity is raised until no token drops: capacity dispatch (jamba's)
    routes a whole slot batch per decode step and the static engine one
    request, so under a binding capacity the two drop different tokens and
    no static stream can match (the dropless deepseek-moe layer is
    unaffected)."""
    arch = dataclasses.replace(smoke_config(name), dtype="float32",
                               param_dtype="float32")
    if arch.moe is not None:
        arch = dataclasses.replace(
            arch, moe=dataclasses.replace(arch.moe, capacity_factor=8.0))
    model = build_model(arch)
    return arch, model, model.init(jax.random.key(0))


def _requests(arch, n=5, seed=7, max_prompt=17):
    """Mixed greedy / seeded-sampled / filtered traffic, ragged lengths."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        prompt = list(map(int, rng.integers(
            5, arch.vocab_size, int(rng.integers(6, max_prompt + 1)))))
        sp = (SamplingParams(),
              SamplingParams(temperature=0.8, seed=100 + i),
              SamplingParams(temperature=0.9, top_k=8, top_p=0.9,
                             seed=200 + i))[i % 3]
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(4, 9)),
                            sampling=sp))
    return reqs


def _serve(model, params, reqs, **kw):
    defaults = dict(num_slots=3, num_pages=64, page_size=4, max_seq_len=64,
                    prefix_cache=False, sanitize=True)
    defaults.update(kw)
    engine = ContinuousEngine(model, params, **defaults)
    res = engine.run(list(reqs))
    return engine, {uid: r["tokens"] for uid, r in res.items()}


def _static_streams(model, params, reqs):
    """Per-request static decode (batch 1, full logits) through the shared
    sampler: the reference the paged engine must reproduce draw for draw."""
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)
    sample = jax.jit(sample_tokens)
    out = {}
    for r in reqs:
        sp, plen = r.sampling, len(r.prompt)

        def draw(logits, pos):
            return int(sample(logits[:, -1],
                              jnp.asarray([sp.seed], jnp.uint32),
                              jnp.asarray([pos], jnp.int32),
                              jnp.asarray([sp.temperature], jnp.float32),
                              jnp.asarray([sp.top_k], jnp.int32),
                              jnp.asarray([sp.top_p], jnp.float32))[0])
        caches = model.init_caches(None, 1, plen + r.max_new_tokens)
        logits, caches = prefill(params, caches,
                                 {"tokens": jnp.asarray([r.prompt])})
        ids = [draw(logits, plen)]
        for s in range(r.max_new_tokens - 1):
            logits, caches = decode(
                params, caches,
                {"tokens": jnp.asarray([[ids[-1]]]),
                 "positions": jnp.full((1,), plen + s, jnp.int32)})
            ids.append(draw(logits, plen + 1 + s))
        out[r.uid] = ids
    return out


@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_fused_decode_bit_parity_all_families(family):
    """Every servable family, in float32, on mixed greedy/sampled/filtered
    traffic with the sanitizer on: the engine's streams equal the static
    engine's (full logits, unfused blocks, the shared sampler), and the
    decode horizon N=4 emits exactly the N=1 streams."""
    arch, model, params = _fp32_model(FAMILY_ARCHS[family])
    # the static engine's SSD scan prefills a whole prompt in one call and
    # takes lengths up to one scan chunk (16) or multiples of it
    reqs = _requests(arch, max_prompt=16)
    e1, toks = _serve(model, params, reqs, decode_steps=1)
    assert e1.fused_decode and e1.fused_decode_off_reason is None
    assert toks == _static_streams(model, params, reqs), \
        f"{family} paged decode diverged from the static engine"
    _, toks4 = _serve(model, params, reqs, decode_steps=4)
    assert toks4 == toks, f"{family} decode diverged at N=4"


def test_fused_decode_preemption_replay_parity():
    """A forced preemption mid-stream under the multi-step engine must
    replay token-identically vs an unpreempted N=1 run: the forced replay
    re-derives every PRNG key from the stream position, and the head
    derives the same ``rs`` uniforms from the same keys."""
    arch, model, params = _smoke_model("llama3.2-3b")
    reqs = [dataclasses.replace(r, max_new_tokens=8)
            for r in _requests(arch, seed=29)]
    _, ref = _serve(model, params, reqs, decode_steps=1)
    engine = ContinuousEngine(model, params, num_slots=3, num_pages=64,
                              page_size=4, max_seq_len=64, prefix_cache=False,
                              sanitize=True, decode_steps=4)
    sched = engine.scheduler
    orig = sched.ensure_capacity
    fired = []

    def forced():
        out = orig()
        victim = next((s for s in sched.running.values()
                       if s.request.uid == 1), None)
        if not fired and victim is not None and not victim.done \
                and len(sched.running) > 1 and len(victim.generated) >= 3:
            sched._preempt(victim)
            out.append(victim)
            fired.append(victim.request.uid)
        return out

    sched.ensure_capacity = forced
    res = engine.run(list(reqs))
    assert fired == [1], "forced preemption must actually fire"
    assert {u: r["tokens"] for u, r in res.items()} == ref, \
        "preempted multi-step stream diverged from N=1"


# ------------------------------------------------------------------ tp = 2 ----

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


_TP_PRELUDE = r"""
import dataclasses
import jax, numpy as np
from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import ContinuousEngine, Request
from repro.serving.sampling import SamplingParams

def requests(arch):
    rng = np.random.default_rng(7)
    return [Request(uid=i,
                    prompt=list(map(int, rng.integers(5, arch.vocab_size,
                                                      10))),
                    max_new_tokens=6,
                    sampling=(SamplingParams() if i % 2 == 0 else
                              SamplingParams(temperature=0.8, top_k=12,
                                             top_p=0.9, seed=100 + i)))
            for i in range(4)]

def serve(arch, **kw):
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    eng = ContinuousEngine(model, params, num_slots=3, num_pages=64,
                           page_size=8, max_seq_len=64, prefix_cache=False,
                           **kw)
    res = eng.run(requests(arch))
    return eng, {u: r["tokens"] for u, r in res.items()}

arch = dataclasses.replace(smoke_config("llama3.2-3b"), num_kv_heads=4,
                           dtype="float32", param_dtype="float32")
"""


def test_tp2_fused_decode_parity_and_shard_gate():
    """tp=2 streams token-identical to the tp=1 engine (the head's
    statistics combine across shards, never logits), with the streaming
    head's shard gate open at both: 512 / 2 is a whole number of 128-wide
    reduction tiles."""
    out = _run_subprocess(_TP_PRELUDE + r"""
eng1, ref = serve(arch, tp=1)
eng2, toks = serve(arch, tp=2)
for eng in (eng1, eng2):
    assert eng.fused_decode and eng.fused_decode_off_reason is None
assert toks == ref, (toks, ref)
print("TP-FUSED-OK")
""")
    assert "TP-FUSED-OK" in out


def test_tp2_misaligned_vocab_serves_materializing_head():
    """pad_vocab(384) = 384, and 384 / 2 = 192 is not a whole number of
    128-wide reduction tiles: the tp=2 engine records why the streaming
    head cannot serve (``fused_decode`` False), serves through the
    materializing head, and still emits the tp=1 engine's streams."""
    out = _run_subprocess(_TP_PRELUDE + r"""
arch = dataclasses.replace(arch, vocab_size=384)
eng1, ref = serve(arch, tp=1)
assert eng1.fused_decode
eng2, toks = serve(arch, tp=2)
assert not eng2.fused_decode
assert "reduction tile" in eng2.fused_decode_off_reason
assert toks == ref, (toks, ref)
print("TP-MATERIALIZED-OK")
""")
    assert "TP-MATERIALIZED-OK" in out


# --------------------------------------------------------- construction gates --

def _abstract(arch):
    model = build_model(arch)
    return model, jax.eval_shape(model.init, jax.random.key(0))


_KW = dict(num_slots=2, num_pages=32, page_size=4, max_seq_len=32,
           prefix_cache=False)


def test_engine_refuses_post_norm_stack():
    """The paged stack defers every residual add into the next pre-norm,
    which a post-norm block does not have: the engine refuses the stack at
    construction instead of serving another path."""
    post = dataclasses.replace(smoke_config("llama3.2-3b"), post_norm=True)
    with pytest.raises(AssertionError, match="pre-norm"):
        ContinuousEngine(*_abstract(post), **_KW)


def test_engine_refuses_mlm_transform_head():
    mlm = dataclasses.replace(smoke_config("llama3.2-3b"), mlm_transform=True)
    with pytest.raises(AssertionError, match="MLM"):
        ContinuousEngine(*_abstract(mlm), **_KW)


def test_tp_fusable_predicate():
    rt = head_ops.RED_TILE
    assert head_ops.tp_fusable(8 * rt, 1)
    assert head_ops.tp_fusable(8 * rt, 2)
    assert head_ops.tp_fusable(8 * rt, 4)
    assert not head_ops.tp_fusable(8 * rt, 3)      # does not divide
    assert not head_ops.tp_fusable(3 * rt, 2)      # slice misses the tile
    assert head_ops.tp_fusable(3 * rt, 3)


# --------------------------------------------------------------- serve CLI ----

def test_serve_cli_reports_the_head():
    """The serve driver returns the engine's head facts, which the chip
    smoke check prints."""
    from repro.launch import serve
    out = serve.main(["--arch", "llama3.2-3b", "--smoke", "--engine",
                      "continuous", "--batch", "2", "--prompt-len", "8",
                      "--gen-len", "3"])
    assert out["fused_decode"] is True
    assert out["fused_decode_off_reason"] is None
