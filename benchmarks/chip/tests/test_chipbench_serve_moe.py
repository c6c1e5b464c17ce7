"""The deepseek-moe-16b serving cell driven end to end on the CPU at a tiny
size of the same structure (one dense layer, then two MoE layers of 8
routed experts of which this chip holds 4, top 2 with raw weights, two
shared experts), past the harness's look for a chip: the engine's prefill
then decode agrees with the plain reference's full forward at the logits; a
sound run comes out correct; a token altered where the decode step produces
it, and the control (the reference in float8), come out not correct.

The limit here is this size's own, set from its readings; the cell's limit
is set from readings at its own size on the chip (``calibrate.py``)."""
import dataclasses
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from lib import common, serve_cell, traffic  # noqa: E402
from lib.precision import mm_fp8  # noqa: E402

LIMIT = 0.05
SEED = 2 ** 35 + 11
TINY = "deepseek-moe-16b-ep4-tiny"
VOCAB = 512


@pytest.fixture(autouse=True)
def tiny_arch(monkeypatch):
    """The registry entry the tiny configuration file names: the cell's
    own share entry with the expert counts cut (the file's sizes set the
    rest)."""
    from repro.configs import REGISTRY
    base = REGISTRY["deepseek-moe-16b-ep4"]
    monkeypatch.setitem(REGISTRY, TINY, dataclasses.replace(
        base, name=TINY, moe=dataclasses.replace(
            base.moe, num_experts=8, top_k=2, expert_ff=32, held_count=4)))


def tiny_cell():
    cell = common.find_cell("deepseek-moe-16b.offline-decode")
    cell.config.update(hidden_size=64, num_hidden_layers=3,
                       num_attention_heads=4, num_key_value_heads=4,
                       head_dim=16, intermediate_size=128,
                       moe_intermediate_size=32, n_routed_experts=4,
                       num_experts_per_tok=2,
                       held_experts={"of": 8},
                       vocab_size=VOCAB, padded_vocab_size=VOCAB,
                       program_arch=TINY)
    cell.config["serving"] = dict(cell.config["serving"], context_limit=256)
    tr = cell.traffic
    tr.update(requests=24, limits={"logit_gap": LIMIT},
              prompt=dict(tr["prompt"], median=24, min=4, max=64),
              output=dict(tr["output"], median=24, min=4, max=64),
              engine={"num_slots": 4, "num_pages": 80},
              in_flight=4,
              check={"min_tokens": 60, "max_requests": 3})
    return cell


def drive(cell):
    return serve_cell.run(cell, SEED, 1.5, False, time.perf_counter(),
                          common.CompileClock())


def test_prefill_then_decode_matches_the_reference_logits():
    """A prompt prefilled in three chunks through the engine's paged path,
    then four tokens decoded through the cache, in float32: the logits at
    every position agree with the reference's full forward pass. Tolerance:
    both sides compute in float32 on the CPU, and differ only in the order
    of their sums (chunked and cached attention, the grouped expert
    combine), about 1e-6 of the logits' scale; 1e-4 leaves room and is far
    below what float8 or bfloat16 products move (the control test)."""
    import jax
    import jax.numpy as jnp

    from repro.models import build_model
    from repro.models import transformer as tf
    cell = tiny_cell()
    cfg = cell.config
    arch = dataclasses.replace(common.program_arch(cfg), dtype="float32")
    model = build_model(arch)
    params = serve_cell.reference_params(cell, SEED)
    page, chunk, n_pages = 16, 16, 8
    pools = tf.init_serving_state(arch, n_pages + 1, page, 1, jnp.float32)
    page_row = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(5, VOCAB, 40).tolist()
    got = []
    for start in range(0, len(prompt), chunk):
        end = min(start + chunk, len(prompt))
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :end - start] = prompt[start:end]
        x = model._embed(params, jnp.asarray(toks))
        x, pools = tf.paged_prefill_stack(
            arch, params["blocks"], pools, x, page_row, jnp.int32(start),
            jnp.int32(end), moe_cap=jnp.int32(0))
    got.append(model._logits(params, tf.chunk_final_hidden(
        x, jnp.int32(start), jnp.int32(end)))[0, 0])
    seq = list(prompt)
    for _ in range(4):
        seq.append(int(jnp.argmax(got[-1])))
        x = model._embed(params, jnp.asarray([[seq[-1]]], jnp.int32))
        x, pools, counts = tf.paged_decode_stack(
            arch, params["blocks"], pools, x, page_row[None],
            jnp.asarray([len(seq) - 1], jnp.int32))
        got.append(model._logits(params, x)[0, 0])
        assert int(counts[0]) <= 2 * 2   # two MoE layers, top 2, one token
    ref_mod = common.reference_module(cell)
    with jax.default_matmul_precision("highest"):
        want = ref_mod.logits(cfg, params, seq)[len(prompt) - 1:]
    got = np.stack([np.asarray(g) for g in got])
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(got, np.asarray(want), rtol=0,
                               atol=1e-4 * scale)


def test_sound_run_is_correct():
    r = drive(tiny_cell())
    assert all(c.ok for c in r.checks), r.checks
    assert r.attempted > 0
    assert r.end_to_end["output_tokens_per_s"] > 0
    assert r.end_to_end["itl_p95_ms"] > 0


def test_token_altered_where_produced_fails(monkeypatch):
    from repro.serving import ContinuousEngine
    decode_fn = ContinuousEngine._decode_fn

    def faulty(self, sampled, filtered):
        step = decode_fn(self, sampled, filtered)

        def altered(*args):
            out = step(*args)
            return ((out[0] + 1) % VOCAB,) + tuple(out[1:])
        return altered

    monkeypatch.setattr(ContinuousEngine, "_decode_fn", faulty)
    r = drive(tiny_cell())
    assert not all(c.ok for c in r.checks), r.checks


@pytest.mark.parametrize("fault", ["experts_zeroed", "experts_shifted"])
def test_fault_in_the_expert_path_fails(monkeypatch, fault):
    """The check sees the held experts: with their output zeroed, or with
    the layer taking the next chip's share of the picks for its own (ids
    offset by the 4 experts it holds), the served tokens are not correct."""
    import jax.numpy as jnp

    from repro.kernels.moe_experts import ops
    from repro.models import moe
    if fault == "experts_zeroed":
        monkeypatch.setattr(ops, "moe_experts",
                            lambda x, *a, **k: jnp.zeros_like(x))
    else:
        offset = moe.held_offset
        monkeypatch.setattr(moe, "held_offset",
                            lambda local_e, tp: offset(local_e, tp) + local_e)
    r = drive(tiny_cell())
    assert not all(c.ok for c in r.checks), r.checks


def test_control_in_float8_fails():
    import jax
    cell = tiny_cell()
    reqs = traffic.generate(cell.traffic, SEED, VOCAB, n=8)
    records = {r.uid: serve_cell.Record(r) for r in reqs}
    ref_mod = common.reference_module(cell)
    params = serve_cell.reference_params(cell, SEED)
    for rec in records.values():   # greedy continuations of the reference
        rec.tokens = []
        seq = list(rec.req.prompt)
        for _ in range(6):
            nxt = int(ref_mod.logits(cell.config, params, seq)[-1].argmax())
            seq.append(nxt)
            rec.tokens.append(nxt)
    sample = list(records.values())
    with jax.default_matmul_precision("highest"):
        sound = serve_cell.served_gaps(ref_mod, cell.config, params, sample)
        control = serve_cell.served_gaps(ref_mod, cell.config, params,
                                         sample, mm=mm_fp8)
    assert max(float(g.max()) for g in sound) <= 1e-5
    assert max(float(g.max()) for g in control) > LIMIT


def test_registry_share_matches_the_configuration_file():
    """``program_arch`` takes the expert layer from the registry entry the
    file names: it holds the file's experts, routes as the file says."""
    from repro.configs import get_config
    cfg = common.find_cell("deepseek-moe-16b.offline-decode").config
    arch = common.program_arch(cfg)
    moe = get_config(cfg["program_arch"]).moe
    assert arch.num_layers == cfg["num_hidden_layers"] == 12
    assert arch.d_ff == cfg["intermediate_size"]
    assert (moe.num_experts, moe.top_k, moe.expert_ff, moe.num_shared_experts,
            moe.first, moe.every) == (
        cfg["held_experts"]["of"], cfg["num_experts_per_tok"],
        cfg["moe_intermediate_size"], cfg["n_shared_experts"],
        cfg["first_k_dense_replace"], cfg["moe_layer_freq"])
    assert moe.norm_topk == cfg["norm_topk_prob"] and moe.dropless
    assert moe.held_count == cfg["n_routed_experts"]
    assert arch.norm_eps == cfg["rms_norm_eps"]
    assert arch.rope_theta == cfg["rope_theta"]
