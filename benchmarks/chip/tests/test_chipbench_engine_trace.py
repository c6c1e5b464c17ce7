"""The readers of the serving engine's spans and programs: exact values on
a trace made up by hand, nothing to read where the program records no
spans or runs no decode program, and the loader on a trace of a tiny engine
recorded on the CPU."""
import statistics
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from lib import common, engine_trace as et_lib, flops, trace  # noqa: E402
from lib.engine_trace import EngineTrace, Span  # noqa: E402
from lib.trace import Event  # noqa: E402

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CFG = common.find_cell("internlm2-1.8b.offline-decode").config
READERS = ("host_ms_per_step.offline", "decode_step_ms.offline",
           "decode_step_roofline.offline")


def _span(name, start, end, **args):
    return Span(name, start, end - start, args)


def _step(i, start, end, *phases):
    return [_span("engine.step", start, end, i=i)] + list(phases)


def made_up() -> EngineTrace:
    """Window [1000, 100000] ns. Steps 0, 1 and 3 decode; step 2 only
    prefills; step 4 decodes but ends after the window."""
    spans = (
        _step(0, 2000, 12000,
              _span("engine.prefill", 2100, 2500, uid=7, tokens=64, final=1),
              _span("engine.prefill_wait", 2500, 2900),
              _span("engine.schedule", 2900, 3000),
              _span("engine.decode", 3000, 4000, slots=2, kv_tokens=100,
                    horizon=1),
              _span("engine.decode_wait", 4000, 9000),
              _span("engine.emit", 9000, 10000))
        + _step(1, 20000, 27000,
                _span("engine.admit", 20100, 20500, uid=8),
                _span("engine.decode", 21000, 22000, slots=3, kv_tokens=210,
                      horizon=1),
                _span("engine.decode_wait", 22000, 26000))
        + _step(2, 30000, 33000,
                _span("engine.prefill", 30100, 30500, uid=8, tokens=64,
                      final=0))
        + _step(3, 50000, 58000,
                _span("engine.decode", 51000, 52000, slots=1, kv_tokens=50,
                      horizon=1),
                _span("engine.decode_wait", 52000, 56000))
        + _step(4, 99000, 101000,
                _span("engine.decode", 100100, 100200, slots=1,
                      kv_tokens=60, horizon=1)))
    modules = [Event("jit_serve_prefill_chunk(9)", 2500, 400),
               Event("jit_serve_decode_step(123)", 4000, 5000),
               Event("jit_serve_decode_step(123)", 22000, 4000),
               Event("jit_serve_decode_step(123)", 52000, 4500),
               Event("jit_serve_decode_step(123)", 100200, 700)]
    return EngineTrace(window=(1000, 100000),
                       spans=sorted(spans, key=lambda s: s.start),
                       modules=modules)


def test_host_ms_per_step():
    # step 0: 10000 - 5000 - 400; step 1: 7000 - 4000; step 3: 8000 - 4000
    assert et_lib.host_ms_per_step(made_up()) == pytest.approx(
        statistics.median([4600, 3000, 4000]) / 1e6, rel=1e-12)


def test_decode_step_ms():
    assert et_lib.decode_step_ms(made_up()) == pytest.approx(4500 / 1e6,
                                                             rel=1e-12)


def test_decode_step_roofline():
    # each slot attends to its cached tokens and the one the step writes
    per_slot = [[51, 51], [71, 71, 71], [51]]
    least = [flops.roofline_seconds(*flops.decode_step(CFG, c), PEAK)[0]
             for c in per_slot]
    want = 100 * (sum(least) / 3) / ((5000 + 4000 + 4500) / 3 / 1e9)
    assert et_lib.decode_step_roofline(made_up(), CFG, PEAK) == \
        pytest.approx(want, rel=1e-12)


def test_multi_step_dispatches():
    t = made_up()
    t.spans = [Span(s.name, s.start, s.dur, dict(s.args, horizon=4))
               if s.name == "engine.decode" else s for s in t.spans]
    t.modules = [Event(e.name.replace("decode_step", "decode_loop"),
                       e.start, e.dur) for e in t.modules]
    assert et_lib.decode_step_ms(t) == pytest.approx(4500 / 1e6, rel=1e-12)
    # a loop's iterations are not in its span: no roofline to read
    assert et_lib.decode_step_roofline(t, CFG, PEAK) is None


def test_nothing_to_read_without_engine_spans_or_decode_programs():
    t = made_up()
    no_spans = EngineTrace(t.window, [], t.modules)
    assert et_lib.host_ms_per_step(no_spans) is None
    assert et_lib.decode_step_roofline(no_spans, CFG, PEAK) is None
    # a program older than the names compiles every step as jit__unknown
    unnamed = EngineTrace(t.window, t.spans, [
        Event(e.name.replace("serve_decode_step", "_unknown"), e.start,
              e.dur) for e in t.modules])
    assert et_lib.decode_step_ms(unnamed) is None
    assert et_lib.decode_step_roofline(unnamed, CFG, PEAK) is None
    no_window = EngineTrace((0, 0), t.spans, t.modules)
    assert et_lib.host_ms_per_step(no_window) is None
    assert et_lib.decode_step_ms(no_window) is None


def test_readers_of_an_untraced_run_read_nothing():
    ctx = {"kind": "serve", "config": CFG, "peaks": PEAK, "num_slots": 16}
    for name in READERS:
        assert common.load_reader(name)(ctx) is None
        assert common.load_reader(name)(dict(ctx, kind="train")) is None


def test_loader_on_a_cpu_trace_of_a_tiny_engine(tmp_path, monkeypatch):
    import jax

    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serving import ContinuousEngine, Request
    arch = smoke_config("llama3.2-3b")
    model = build_model(arch)
    engine = ContinuousEngine(model, model.init(jax.random.key(0)),
                              num_slots=2, num_pages=32, page_size=4,
                              max_seq_len=32)
    reqs = [Request(uid=i, prompt=[5 + i] * (6 + i), max_new_tokens=5)
            for i in range(3)]
    out = tmp_path / "trace" / "cell"
    jax.profiler.start_trace(str(out))
    try:
        with common.span("engine_run"):
            engine.run(reqs)
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(common, "OUT_DIR", tmp_path)
    et_lib._load_cached.cache_clear()
    ctx = {"kind": "serve", "config": CFG, "peaks": PEAK,
           "reduced": trace.Reduced(1.0, 1.0, {}, {}, {}, [])}
    t = et_lib.for_run(ctx)
    assert t is et_lib.for_run(ctx)             # loaded once
    decodes = t.named("engine.decode")
    assert len(decodes) == engine.decode_dispatches
    assert decodes[0].args == {"slots": 1, "kv_tokens": 6, "horizon": 1}
    assert [s.args["i"] for s in t.named("engine.step")] == list(
        range(len(t.named("engine.step"))))
    assert et_lib.host_ms_per_step(t) > 0
    # the CPU has no XLA Modules line: no device time to read
    assert t.modules == []
    assert common.load_reader("decode_step_ms.offline")(ctx) is None
    assert common.load_reader("host_ms_per_step.offline")(ctx) == \
        et_lib.host_ms_per_step(t)
