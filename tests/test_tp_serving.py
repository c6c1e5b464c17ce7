"""Tensor-parallel continuous serving: token-identical streams across
tp ∈ {1, 2, 4} (greedy + seeded-sampled, including forced-replay preemption
and a CoW tail), head-sharded pool/param specs, and engine validation.

Parity runs in a subprocess with 4 forced host devices (the pattern
``test_sharding.py`` established), so it executes in the plain tier-1 run
too — the ``tier1-multidevice`` CI job additionally runs this whole file
in-process under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
Parity uses fp32, like the cross-engine sampled-parity tests: bf16's
reassociated psum summation flips near-tied draws of the random-init smoke
model, which is rounding noise, not layout divergence.
"""
import subprocess
import sys
from pathlib import Path

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.models import build_model
from repro.parallel import sharding as sh

ROOT = Path(__file__).resolve().parents[1]


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


# -------------------------------------------------------------------- parity ----

def test_tp_parity_greedy_sampled_and_preemption():
    """One subprocess covers the whole acceptance matrix: mixed
    greedy/sampled traffic token-identical across tp=1/2/4 and to the
    default (pre-TP) engine construction, then a starved pool forcing
    preemption replay (+ a shared prefix exercising the CoW tail copy)
    token-identical at tp=2 — with TP collective accounting non-zero only
    at tp > 1."""
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
rng = np.random.default_rng(7)
prompts = [list(map(int, rng.integers(5, arch.vocab_size,
                                      int(rng.integers(4, 14)))))
           for _ in range(5)]
gens = [int(rng.integers(3, 9)) for _ in range(5)]
sps = [SamplingParams() if i % 2 == 0 else
       SamplingParams(temperature=0.8, top_k=12, top_p=0.9, seed=100 + i)
       for i in range(5)]
reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=gens[i],
                sampling=sps[i]) for i in range(5)]

def serve(**kw):
    eng = ContinuousEngine(model, params, num_slots=4, num_pages=64,
                           page_size=8, max_seq_len=64, **kw)
    res = eng.run(list(reqs))
    return eng, [res[i]["tokens"] for i in range(5)]

eng0, ref = serve()                       # default ctor == the pre-TP engine
assert any(len(t) for t in ref)
eng1, r1 = serve(tp=1)
assert r1 == ref and eng1.collective_bytes == 0
for tp in (2, 4):
    eng, toks = serve(tp=tp)
    assert toks == ref, (tp, toks, ref)
    assert eng.collective_bytes > 0
    stats = eng.tp_stats()
    assert stats["tp"] == tp and stats["per_device"]["kv_bytes"] > 0

# starved pool: forced-replay preemption + prefix cache + CoW tail, tp=2
rng = np.random.default_rng(37)
shared = list(map(int, rng.integers(5, arch.vocab_size, 10)))
pp = [shared + list(map(int, rng.integers(5, arch.vocab_size,
                                          int(rng.integers(2, 6)))))
      for _ in range(5)]
pg = [4, 16, 7, 12, 9]
ps = [SamplingParams(temperature=0.8, top_k=0 if i % 2 else 20, top_p=0.95,
                     seed=1000 + i) for i in range(5)]
preqs = [Request(uid=i, prompt=pp[i], max_new_tokens=pg[i], sampling=ps[i])
         for i in range(5)]

def starved(tp):
    eng = ContinuousEngine(model, params, num_slots=2, num_pages=10,
                           page_size=4, max_seq_len=40, tp=tp)
    res = eng.run(list(preqs))
    return eng, [res[i]["tokens"] for i in range(5)]

e1, s1 = starved(1)
e2, s2 = starved(2)
assert s1 == s2, (s1, s2)
assert e2.prefills > 5, "pool was not starved enough to preempt"
assert e2.cow_copies > 0, "shared tail never took the CoW path"
print("TP_PARITY_OK")
""")
    assert "TP_PARITY_OK" in out


def test_tp2_fused_vs_reference_sampler_parity():
    """The streaming filter and the sort-based oracle swapped in for it
    must emit bit-identical sampled streams at tp=2 (logits are replicated
    post-psum, so the filter sees the same rows on every shard): tp=2 ==
    oracle tp=2 == tp=1."""
    out = _run_subprocess(r"""
import dataclasses
import jax, numpy as np
from repro.configs import smoke_config
from repro.kernels.fused_sampling import ops as filt_ops
from repro.kernels.fused_sampling import ref as filt_ref
from repro.models import build_model
from repro.serving import ContinuousEngine, Request
from repro.serving.sampling import SamplingParams

arch = dataclasses.replace(smoke_config("llama3.2-3b"), num_kv_heads=4,
                           dtype="float32", param_dtype="float32")
model = build_model(arch)
params = model.init(jax.random.key(0))
rng = np.random.default_rng(61)
prompts = [list(map(int, rng.integers(5, arch.vocab_size,
                                      int(rng.integers(4, 12)))))
           for _ in range(4)]
gens = [int(rng.integers(4, 9)) for _ in range(4)]
sps = [SamplingParams(temperature=0.9, top_k=16 if i % 2 else 0,
                      top_p=0.85, seed=500 + i) for i in range(4)]
reqs = [Request(uid=i, prompt=prompts[i], max_new_tokens=gens[i],
                sampling=sps[i]) for i in range(4)]

def serve(tp):
    eng = ContinuousEngine(model, params, num_slots=4, num_pages=64,
                           page_size=8, max_seq_len=64, tp=tp)
    res = eng.run(list(reqs))
    return [res[i]["tokens"] for i in range(4)]

ref = serve(1)
assert serve(2) == ref, "tp=2 diverged from tp=1"
filt_ops.filter_logits = filt_ref.filter_logits_ref
assert serve(2) == ref, "tp=2 through the oracle filter diverged"
print("TP2_SAMPLER_PARITY_OK")
""")
    assert "TP2_SAMPLER_PARITY_OK" in out


# --------------------------------------------------------- validation (1 dev) ---

def test_tp_rejects_indivisible_head_counts():
    arch = smoke_config("llama3.2-3b")        # 4 query heads, 2 kv heads
    model = build_model(arch)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    with pytest.raises(AssertionError, match="head"):
        # 3 divides neither head count — must fail before any mesh is built
        # (so the error names the arch, not the device count)
        from repro.serving import ContinuousEngine
        ContinuousEngine(model, params, tp=3)


def test_split_fused_qkv_is_exact():
    """Splitting the fused wqkv into wq/wk/wv must not change one projection
    output bit — it is the tp > 1 engine's precondition for head sharding."""
    import jax.numpy as jnp
    from repro.models.attention import qkv_project
    from repro.serving.engine import _split_fused_qkv

    arch = smoke_config("qwen2-vl-2b")        # fused qkv WITH biases
    model = build_model(arch)
    params = model.init(jax.random.key(3))
    split = _split_fused_qkv(params, arch)
    flat = jax.tree_util.tree_leaves_with_path(split)
    names = {kp[-1].key for kp, _ in flat if hasattr(kp[-1], "key")}
    assert "wqkv" not in names and {"wq", "wk", "wv"} <= names

    def first_attn(tree):
        blocks = tree["blocks"]
        blk = blocks["period_0"] if "period_0" in blocks else blocks
        return blk["layer_0"]["attn"]

    fused, sep = first_attn(params), first_attn(split)
    if fused["wqkv"].ndim == 3:                # scanned stack: take period 0
        fused = jax.tree.map(lambda a: a[0], fused)
        sep = jax.tree.map(lambda a: a[0], sep)
    x = jax.random.normal(jax.random.key(4), (2, 3, arch.d_model),
                          jnp.float32)
    for a, b in zip(qkv_project(arch, fused, x), qkv_project(arch, sep, x)):
        assert jnp.array_equal(a, b)


def test_serving_param_pspecs_layout():
    """The TP serving spec table: projections sharded Megatron-style,
    everything that must stay replicated (embedding, lm head, norms,
    row-parallel biases) replicated — the invariant that makes logits and
    sampler draws identical on every shard."""
    from repro.serving.engine import _split_fused_qkv

    arch = smoke_config("qwen2-vl-2b")
    model = build_model(arch)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    with pytest.raises(ValueError, match="fused"):
        sh.serving_param_pspecs(params)        # fused wqkv must be rejected
    split = jax.eval_shape(lambda: _split_fused_qkv(
        model.init(jax.random.key(0)), arch))
    specs = sh.serving_param_pspecs(split)

    seen = {}
    for kp, spec in jax.tree_util.tree_leaves_with_path(
            specs, is_leaf=lambda s: isinstance(s, P)):
        name = kp[-1].key
        seen.setdefault(name, spec)
    assert seen["wq"][-1] == "model" and seen["wv"][-1] == "model"
    assert seen["wo"][-2] == "model" and seen["wo"][-1] is None
    assert seen["w1"][-1] == "model" and seen["w2"][-2] == "model"
    assert seen["bq"][-1] == "model"
    # replicated: anything whose value feeds a post-psum (or logits) path
    for name in ("embedding", "scale", "bo", "b2"):
        if name in seen:
            assert all(a is None for a in seen[name]), (name, seen[name])


def test_paged_pool_pspecs_shard_head_axis():
    import jax.numpy as jnp
    from repro.models import transformer as tf

    for name in ("llama3.2-3b", "internlm2-1.8b"):
        arch = smoke_config(name)
        pools = jax.eval_shape(
            lambda a=arch: tf.init_paged_caches(a, 8, 4, jnp.float32))
        specs = sh.paged_pool_pspecs(pools)
        for spec, leaf in zip(
                jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P)),
                jax.tree.leaves(pools)):
            assert spec[leaf.ndim - 2] == "model"       # the Hkv axis
            assert all(a is None for i, a in enumerate(spec)
                       if i != leaf.ndim - 2)
