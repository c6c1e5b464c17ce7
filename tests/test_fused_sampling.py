"""Fused sort-free sampling filter vs its sort-based oracle.

Three layers of parity, all bit-exact:

1. kernel level — the streaming jnp bisection path, the Pallas kernel in
   interpret mode, and the one-sort reference must produce identical masked
   logits on adversarial inputs (ties at the k-th value, ``top_p = 1.0``,
   ``top_k >= V``, pre-masked ``-inf`` entries, all-``-inf`` rows,
   float32-tight nucleus boundaries, signed zeros): hypothesis sweep plus a
   pinned no-hypothesis instance per edge case.
2. sampler level — ``sample_tokens`` draws the tokens that the sort-based
   oracle filter plus the same canonical draw (``_oracle_tokens``) picks,
   and the filter agrees with the retired twin-sort implementation (kept
   verbatim below as ``_legacy_filter``) away from its float32 cumsum
   boundaries.
3. engine level — the continuous engine serves the same sampled token
   streams with its filter and with the oracle swapped in for it.

Conventions mirror ``test_sampling.py`` (optional hypothesis with a pinned
fallback, the fp32 smoke llama fixture, ``_mixed_requests``-style traffic).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep (requirements-dev.txt)
    given = settings = st = None

from repro.configs import smoke_config
from repro.kernels.fused_lm_head import ref as head_ref
from repro.kernels.fused_sampling import kernel, ops, ref
from repro.models import build_model
from repro.serving import (ContinuousEngine, Request, SamplingParams,
                           sample_tokens)

V = 512          # smoke vocab


# ----------------------------------------------------------- case generators --

def _case(seed: int, *, ties=False, neg_inf=False, dead_row=False,
          signed_zeros=False, scale=1.0, rows=4, vocab=V):
    """One adversarial (logits, top_k, top_p) instance."""
    rng = np.random.default_rng(seed)
    lg = rng.normal(size=(rows, vocab)).astype(np.float32) * scale
    if ties:
        lg = np.round(lg * 2) / 2           # massive duplication, ties at kth
    if neg_inf:
        lg[rng.random(size=lg.shape) < 0.3] = -np.inf
    if dead_row:
        lg[0, :] = -np.inf
    if signed_zeros:
        lg[1, :8] = 0.0
        lg[1, 8:16] = -0.0
    top_k = rng.integers(-1, vocab + 100, size=rows).astype(np.int32)
    top_p = rng.choice([0.3, 0.9, 0.95, 0.999, 1.0],
                       size=rows).astype(np.float32)
    return jnp.asarray(lg), jnp.asarray(top_k), jnp.asarray(top_p)


def _assert_threeway(lg, top_k, top_p):
    """ref oracle == streaming jnp path == Pallas kernel (interpret), bit
    for bit (NaN patterns compared as equal — thresholds may round-trip a
    non-signalling pattern, the masks never differ)."""
    a = np.asarray(ref.filter_logits_ref(lg, top_k, top_p))
    b = np.asarray(ops._filter_logits_jnp(lg, top_k, top_p))
    c = np.asarray(kernel.filter_logits(lg, top_k, top_p, interpret=True))
    assert np.array_equal(a, b, equal_nan=True), "jnp path diverged from ref"
    assert np.array_equal(a, c, equal_nan=True), "pallas kernel diverged"
    return a


# --------------------------------------------------- hypothesis parity sweep --

if st is not None:
    @settings(max_examples=25, deadline=None)
    @given(
        seed=st.integers(0, 100_000),
        ties=st.booleans(),
        neg_inf=st.booleans(),
        dead_row=st.booleans(),
        signed_zeros=st.booleans(),
        scale=st.sampled_from([1.0, 5.0, 30.0]),
    )
    def test_filter_parity_property_sweep(seed, ties, neg_inf, dead_row,
                                          signed_zeros, scale):
        _assert_threeway(*_case(seed, ties=ties, neg_inf=neg_inf,
                                dead_row=dead_row,
                                signed_zeros=signed_zeros, scale=scale))
else:
    def test_filter_parity_property_sweep():
        pytest.importorskip("hypothesis")


# -------------------------------------------- pinned no-hypothesis instances --

def test_parity_ties_at_kth_value():
    """Quantized logits put duplicates exactly at the k-th largest value;
    the filter is a value threshold, so every tie is kept — identically in
    all three implementations."""
    lg, _, _ = _case(11, ties=True)
    top_k = jnp.full((4,), 7, jnp.int32)
    top_p = jnp.ones((4,), jnp.float32)
    out = _assert_threeway(lg, top_k, top_p)
    kept = (out > -np.inf).sum(axis=-1)
    lg_np = np.asarray(lg)
    for r in range(4):
        kth = np.sort(lg_np[r])[::-1][6]
        assert kept[r] == (lg_np[r] >= kth).sum()     # ties included
        assert kept[r] >= 7


def test_parity_top_p_disabled_keeps_topk_support():
    """top_p = 1.0 must be an exact no-op on the top-k-masked row (the
    historical sampler guaranteed this explicitly; the threshold form pins
    the threshold at -inf)."""
    lg, _, _ = _case(12)
    top_k = jnp.asarray([5, 0, 513, 1], jnp.int32)
    top_p = jnp.ones((4,), jnp.float32)
    out = _assert_threeway(lg, top_k, top_p)
    kept = (out > -np.inf).sum(axis=-1)
    assert list(kept) == [5, V, V, 1]


def test_parity_top_k_at_least_vocab_is_noop():
    lg, _, _ = _case(13)
    for k in (V, V + 1, 10_000, 0, -3):
        out = _assert_threeway(lg, jnp.full((4,), k, jnp.int32),
                               jnp.ones((4,), jnp.float32))
        assert np.array_equal(out, np.asarray(lg))


def test_parity_premasked_neg_inf_rows():
    lg, tk, tp = _case(14, neg_inf=True)
    _assert_threeway(lg, tk, tp)


def test_parity_fully_masked_row_passes_through():
    """An all--inf row has zero mass: no threshold can bind, the row comes
    back unchanged (and the categorical draw downstream is identical for
    both implementations because the masked logits are)."""
    lg, _, _ = _case(15, dead_row=True)
    out = _assert_threeway(lg, jnp.full((4,), 10, jnp.int32),
                           jnp.full((4,), 0.5, jnp.float32))
    assert (out[0] == -np.inf).all()


def test_parity_signed_zero_boundary():
    """-0.0 and +0.0 straddle the bit-key order but compare equal as
    floats; whatever threshold the bisections land on, the masks must
    agree."""
    lg, _, _ = _case(16, signed_zeros=True, scale=0.001)
    for tp in (0.3, 0.5, 0.9, 1.0):
        _assert_threeway(lg, jnp.full((4,), 0, jnp.int32),
                         jnp.full((4,), tp, jnp.float32))


def test_parity_float32_tight_nucleus_boundary():
    """Geometric rows where the cumulative mass hits top_p exactly (0.5 +
    0.25 + ... with top_p on the partial sums): the classic spot where two
    float32 cumsum orders disagree by one token. The shared strict-greater-
    mass predicate makes all three implementations cut identically."""
    lg = np.full((4, V), -np.inf, np.float32)
    lg[:, :16] = np.log(2.0) * -np.arange(16)       # probs 1/2^i (unnorm)
    lg = jnp.asarray(lg)
    for tp in (0.5, 0.75, 0.875, 0.8749999, 0.8750001):
        _assert_threeway(lg, jnp.zeros((4,), jnp.int32),
                         jnp.full((4,), tp, jnp.float32))


def test_parity_pinned_smoke_without_hypothesis():
    """One pinned instance of the property sweep (runs without hypothesis),
    plus the underflow-tail scale the sweep samples."""
    _assert_threeway(*_case(4321, ties=True, neg_inf=True, scale=30.0))
    _assert_threeway(*_case(1234, dead_row=True, signed_zeros=True))


# ------------------------------------------- legacy twin-sort sampler parity --

def _legacy_filter(lg, top_k, top_p):
    """The retired twin-sort filter, verbatim from the old
    ``serving.sampling.sample_tokens`` — the semantics the fused filter
    replaced (top-k value threshold + float32-cumsum nucleus)."""
    lg = jnp.asarray(lg, jnp.float32)
    vocab = lg.shape[-1]
    k = jnp.where(top_k <= 0, vocab, jnp.minimum(top_k, vocab))
    kth = jnp.take_along_axis(jnp.sort(lg, axis=-1), (vocab - k)[:, None],
                              axis=-1)
    lg = jnp.where(lg < kth, -jnp.inf, lg)
    desc = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    tp = top_p.astype(jnp.float32)[:, None]
    keep = ((cum - probs) < tp) | (tp >= 1.0)
    cutoff = jnp.maximum(jnp.sum(keep, axis=-1) - 1, 0)
    thresh = jnp.take_along_axis(desc, cutoff[:, None], axis=-1)
    return jnp.where(lg < thresh, -jnp.inf, lg)


def test_fused_matches_legacy_sampler_on_generic_logits():
    """Away from float32 nucleus-boundary rounding (generic continuous
    logits — pinned seeds, verified clear of the boundary) the fused filter
    keeps exactly the support the twin-sort implementation kept. This pins
    the redefinition of the cut from "f32 cumsum rank" to "strict-greater
    mass" as a rounding-level change, not a semantic one."""
    for seed in (0, 1, 2, 3, 4, 5):
        lg, tk, _ = _case(seed)
        tp = jnp.full((4,), 0.9, jnp.float32)
        legacy = np.asarray(_legacy_filter(lg, tk, tp))
        fused = np.asarray(ops.filter_logits(lg, tk, tp))
        assert np.array_equal(legacy, fused), f"seed {seed}"


# ---------------------------------------------------- sample_tokens bit parity -

def _arrs(rows, seed=0, pos=0, temp=1.0, top_k=0, top_p=1.0):
    def vec(v, dt):
        a = np.asarray(v, dt)
        return jnp.asarray(np.broadcast_to(a, (rows,)))
    return (vec(seed, np.uint32), vec(pos, np.int32),
            vec(temp, np.float32), vec(top_k, np.int32),
            vec(top_p, np.float32))


def _oracle_tokens(logits, seeds, positions, temps, top_k, top_p):
    """``sample_tokens`` rebuilt from its oracles: temperature scaling, the
    sort-based reference filter, then the canonical inverse-CDF draw;
    temperature-0 rows take the raw argmax."""
    temps = temps.astype(jnp.float32)
    lg = logits.astype(jnp.float32) / jnp.where(temps > 0, temps, 1.0)[:, None]
    lg = ref.filter_logits_ref(lg, top_k, top_p)
    drawn = head_ref.draw_tokens(lg, head_ref.row_uniforms(seeds, positions))
    return jnp.where(temps > 0, drawn,
                     jnp.argmax(logits, axis=-1).astype(jnp.int32))


def test_sample_tokens_fused_flag_is_token_invisible():
    """``sample_tokens`` draws what the oracle filter plus the same draw
    picks, across seeds, positions, and filter settings: the streaming
    filter changes speed, never tokens."""
    rng = np.random.default_rng(8)
    logits = jnp.asarray(rng.normal(size=(6, V)).astype(np.float32))
    for pos in range(12):
        for tk, tp in ((40, 0.95), (8, 1.0), (0, 0.7), (1, 0.5)):
            args = _arrs(6, seed=range(6), pos=pos, temp=0.8, top_k=tk,
                         top_p=tp)
            a = sample_tokens(logits, *args)
            b = _oracle_tokens(logits, *args)
            assert (np.asarray(a) == np.asarray(b)).all(), (pos, tk, tp)


@pytest.mark.parametrize("top_p", [0.5, 1.0])
@pytest.mark.parametrize("top_k", [0, 1, V])
def test_sample_tokens_matches_reference_filter_corners(top_k, top_p):
    """The filter's corners — top_k off, 1 and the whole vocab, against a
    binding and an off nucleus — with greedy and sampled rows co-batched:
    ``sample_tokens`` equals the oracle filter plus the same draw at every
    position."""
    rng = np.random.default_rng(100 + top_k)
    logits_np = rng.normal(size=(4, V)).astype(np.float32) * 3.0
    logits = jnp.asarray(logits_np)
    for pos in range(8):
        args = _arrs(4, seed=[3, 4, 5, 6], pos=pos,
                     temp=[0.0, 0.6, 1.0, 1.7], top_k=top_k, top_p=top_p)
        a = np.asarray(sample_tokens(logits, *args))
        b = np.asarray(_oracle_tokens(logits, *args))
        assert (a == b).all(), (pos, a, b)
        assert a[0] == np.argmax(logits_np[0])          # the greedy row
        if top_k == 1:
            assert (a == np.argmax(logits_np, -1)).all()


def test_sample_tokens_fused_temp_zero_is_bitwise_argmax():
    rng = np.random.default_rng(9)
    logits = jnp.asarray(rng.normal(size=(5, V)).astype(np.float32))
    toks = sample_tokens(logits, *_arrs(5, temp=0.0, top_k=40, top_p=0.9))
    assert (np.asarray(toks) == np.argmax(np.asarray(logits), -1)).all()


def test_sample_tokens_fused_restricts_support():
    """The fused path enforces the filters it claims to: top-k draws stay in
    the top-k set, nucleus draws in the nucleus."""
    rng = np.random.default_rng(10)
    logits_np = rng.normal(size=(1, 64)).astype(np.float32)
    logits = jnp.asarray(logits_np)
    top = set(np.argsort(logits_np[0])[-5:])
    drawn = set()
    for pos in range(40):
        toks = sample_tokens(logits, *_arrs(1, seed=9, pos=pos, temp=1.5,
                                            top_k=5))
        drawn.add(int(toks[0]))
    assert drawn <= top and len(drawn) > 1


# ------------------------------------------------------- engine-level parity --

@pytest.fixture(scope="module")
def fp32_llama():
    arch = smoke_config("llama3.2-3b")
    arch = dataclasses.replace(arch, dtype="float32", param_dtype="float32")
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    return arch, model, params


def _sampled_requests(arch, rng, n=4):
    reqs = []
    for i in range(n):
        prompt = list(map(int, rng.integers(5, arch.vocab_size,
                                            int(rng.integers(4, 14)))))
        sp = SamplingParams(temperature=0.8, top_k=40, top_p=0.95,
                            seed=int(rng.integers(2 ** 31)))
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(4, 9)),
                            sampling=sp))
    return reqs


def test_fused_and_reference_engines_serve_identical_streams(fp32_llama,
                                                           monkeypatch):
    """The engine serves the same sampled streams with its streaming filter
    and with the sort-based oracle swapped in for it, and compiles one
    filtered decode variant either way."""
    arch, model, params = fp32_llama
    rng = np.random.default_rng(51)
    reqs = _sampled_requests(arch, rng)
    kw = dict(num_slots=4, num_pages=48, page_size=8, max_seq_len=64,
              prefix_cache=False)
    tokens = {}
    for oracle in (False, True):
        if oracle:
            monkeypatch.setattr(ops, "filter_logits", ref.filter_logits_ref)
        engine = ContinuousEngine(model, params, **kw)
        res = engine.run([dataclasses.replace(r) for r in reqs])
        tokens[oracle] = [res[i]["tokens"] for i in range(len(reqs))]
        assert ("decode", True, True) in engine._jit_cache
    assert tokens[False] == tokens[True], \
        "streaming filter diverged from the sort-based oracle in serving"
