"""A serving cell driven end to end on the CPU at a tiny size, past the
harness's look for a chip: a sound run comes out correct; a token altered
where the decode step produces it, and the control (the plain reference in
float8, put in the program's place), come out not correct.

The limit here is this size's own, set from its readings; the cell's limit
is set from readings at its own size on the chip (``calibrate.py``)."""
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from lib import common, serve_cell, traffic  # noqa: E402
from lib.precision import mm_fp8  # noqa: E402

LIMIT = 0.05
SEED = 2 ** 35 + 9


def tiny_cell():
    cell = common.find_cell("internlm2-1.8b.offline-decode")
    cell.config.update(hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, num_key_value_heads=2,
                       head_dim=16, intermediate_size=128, vocab_size=512,
                       padded_vocab_size=512)
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
            return ((out[0] + 1) % 512,) + tuple(out[1:])
        return altered

    monkeypatch.setattr(ContinuousEngine, "_decode_fn", faulty)
    r = drive(tiny_cell())
    assert not all(c.ok for c in r.checks), r.checks


def test_control_in_float8_fails():
    import jax
    cell = tiny_cell()
    reqs = traffic.generate(cell.traffic, SEED, 512, n=8)
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
