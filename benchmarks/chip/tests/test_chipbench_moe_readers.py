"""The MoE cell's counts and readers: the hand counts of the expert kernel
and of a decode step, exact values of ``moe_experts_roofline.offline``,
``mfu_moe.offline`` and ``decode_step_roofline_moe.offline`` on a trace
made up by hand, and nothing to read from a
program whose ``engine.emit`` carries no expert counters (one older than
them) or whose decode step names no expert kernel."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from lib import common, engine_trace, flops, moe_flops, trace  # noqa: E402
from lib.engine_trace import EngineTrace, Span  # noqa: E402
from lib.trace import Event  # noqa: E402

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CFG = common.find_cell("deepseek-moe-16b.offline-decode").config
KERNEL = "moe_experts.3 = f32[352,2048]"


def test_hand_counts():
    d, f = 2048, 1408
    assert moe_flops.moe_layers(CFG) == 11
    assert moe_flops.expert_kernel(CFG, 10, 4) == (
        2.0 * 3 * d * f * 10, 4 * 3 * d * f * 2 + 10 * d * 6)
    attn = 4 * d * 16 * 128
    per_token = (12 * attn + 3 * d * 10944 + 11 * (d * 64 + 3 * d * 2 * f)
                 + d * 102400)
    assert moe_flops.dense_params_per_token(CFG) == per_token
    fl, nbytes = moe_flops.decode_step(CFG, [100, 50], 9, 5)
    assert fl == pytest.approx(
        2 * 2.0 * per_token + 4.0 * 12 * 16 * 128 * 150
        + 2.0 * 3 * d * f * 9, rel=1e-15)
    assert nbytes == (per_token + 5 * 3 * d * f) * 2 \
        + 12 * 2 * 16 * 128 * 2 * 150


def _span(name, start, end, **args):
    return Span(name, start, end - start, args)


def made_up(counters=True) -> EngineTrace:
    """Two decode steps with their counters; a prefill-only step between."""
    def emit(start, end, rows, touched):
        args = dict(expert_rows=rows, experts_touched=touched) \
            if counters else {}
        return _span("engine.emit", start, end, **args)
    spans = [
        _span("engine.step", 2000, 12000, i=0),
        _span("engine.decode", 3000, 4000, slots=2, kv_tokens=100,
              horizon=1),
        _span("engine.decode_wait", 4000, 9000),
        emit(9000, 10000, 40, 20),
        _span("engine.step", 20000, 23000, i=1),
        _span("engine.prefill", 20100, 20500, uid=8, tokens=64, final=0),
        _span("engine.step", 30000, 40000, i=2),
        _span("engine.decode", 31000, 32000, slots=3, kv_tokens=210,
              horizon=1),
        _span("engine.decode_wait", 32000, 36000),
        emit(36000, 37000, 66, 30)]
    # the two decode steps' programs, and a prefill chunk's
    modules = [Event("jit_serve_decode_step(1a2b)", 4000, 4_000_000),
               Event("jit_serve_prefill_chunk(3c4d)", 20500, 2_000_000),
               Event("jit_serve_decode_step(1a2b)", 32000, 2_000_000)]
    return EngineTrace(window=(1000, 10_000_000), spans=spans,
                       modules=modules)


def _ctx(monkeypatch, t, kernels=True):
    monkeypatch.setattr(engine_trace, "for_run", lambda ctx: t)
    r = trace.Reduced(window_s=0.5, busy_s=0.4,
                      op_seconds={KERNEL: 0.002, "fusion.1 = f32[2]": 0.1},
                      op_text={}, op_count={KERNEL: 22, "fusion.1 = f32[2]": 4},
                      idle_gaps=[])
    return {"kind": "serve", "config": CFG, "peaks": PEAK, "reduced": r,
            "kernels": {KERNEL: "_moe_experts_kernel"} if kernels else {}}


def test_moe_readers_on_a_made_up_trace(monkeypatch):
    ctx = _ctx(monkeypatch, made_up())
    least = [flops.roofline_seconds(*moe_flops.expert_kernel(CFG, r, t),
                                    PEAK)[0] for r, t in ((40, 20), (66, 30))]
    # 22 kernel calls at 11 MoE layers a step are 2 steps
    assert common.load_reader("moe_experts_roofline.offline")(ctx) == \
        pytest.approx(100 * (sum(least) / 2) * 2 / 0.002, rel=1e-12)
    steps = [([102, 0], 40, 20), ([213, 0, 0], 66, 30)]
    least = sum(flops.roofline_seconds(
        *moe_flops.decode_step(CFG, c, r, t), PEAK)[0] for c, r, t in steps)
    assert common.load_reader("mfu_moe.offline")(ctx) == pytest.approx(
        100 * least / 0.5, rel=1e-12)
    # the mean least time of the two steps over their mean device time, 3 ms
    assert common.load_reader("decode_step_roofline_moe.offline")(ctx) == \
        pytest.approx(100 * (least / 2) / 3e-3, rel=1e-12)


def test_moe_readers_read_nothing_without_counters_or_kernel(monkeypatch):
    ctx = _ctx(monkeypatch, made_up(counters=False))
    for name in ("moe_experts_roofline.offline", "mfu_moe.offline",
                 "decode_step_roofline_moe.offline"):
        assert common.load_reader(name)(ctx) is None
    ctx = _ctx(monkeypatch, made_up(), kernels=False)
    assert common.load_reader("moe_experts_roofline.offline")(ctx) is None
    monkeypatch.setattr(engine_trace, "for_run", lambda ctx: None)
    for name in ("moe_experts_roofline.offline", "mfu_moe.offline",
                 "decode_step_roofline_moe.offline"):
        assert common.load_reader(name)({"kind": "serve", "config": CFG,
                                         "peaks": PEAK}) is None
