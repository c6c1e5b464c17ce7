"""The arithmetic of the end-to-end and per-layer metrics on records and
traces made up by hand."""
import math
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import common, flops, serve_cell, trace  # noqa: E402
from lib import traffic as traffic_lib  # noqa: E402
from lib.traffic import TrafficRequest  # noqa: E402

PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
CFG = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16,
       "vocab_size": 10, "padded_vocab_size": 16, "gated_mlp": True}


def rec(uid, prompt_len, times, arrival=0.0, greedy=False):
    r = serve_cell.Record(TrafficRequest(uid, [5] * prompt_len, 10, arrival,
                                         greedy, 0))
    r.times = list(times)
    r.tokens = [7] * len(times)
    return r


def test_percentile_is_nearest_rank():
    assert serve_cell.percentile([5, 1, 4, 2, 3], 50) == 3
    assert serve_cell.percentile(list(range(1, 21)), 95) == 19
    assert serve_cell.percentile([1, math.inf], 95) == math.inf
    assert math.isnan(serve_cell.percentile([], 95))


def test_window_metrics():
    records = {0: rec(0, 3, [0.5, 1.0, 1.5, 2.5]),
               1: rec(1, 4, [1.2, 1.9], arrival=1.0),
               2: rec(2, 4, [], arrival=1.5)}
    m = serve_cell.window_metrics(records, 2.0)
    assert m["tokens"] == 5                       # 2.5 is past the window
    assert sorted(m["gaps"]) == pytest.approx([0.5, 0.5, 0.7])
    assert m["output_tokens_per_s"] == 2.5
    assert m["ttfts"][:2] == pytest.approx([0.5, 0.2])
    assert math.isinf(m["ttfts"][2])              # never served: infinite
    assert m["itl_p95_ms"] == pytest.approx(700.0)


def test_window_metrics_of_a_window_opened_later():
    records = {0: rec(0, 3, [0.5, 1.0, 1.5, 2.5]),
               1: rec(1, 4, [1.2, 1.9, 3.5])}
    m = serve_cell.window_metrics(records, 2.0, start=1.0, open_loop=False)
    assert m["tokens"] == 4             # 0.5, 1.0 before; 3.5 after
    assert sorted(m["gaps"]) == pytest.approx([0.7, 1.0])
    assert m["output_tokens_per_s"] == 2.0
    assert "ttft_p95_ms" not in m and m["ttfts"] == []


def test_in_flight_head_keeps_every_length():
    tr = {"requests": 40, "arrival": "backlog", "lengths_seed": 3,
          "prompt": {"median": 50, "sigma": 0.5, "min": 8, "max": 200},
          "output": {"median": 60, "sigma": 0.5, "min": 8, "max": 200}}
    plain = traffic_lib.generate(tr, 11, 1000)
    head = traffic_lib.generate(dict(tr, in_flight=8), 2 ** 40 + 1, 1000)
    for a, b in zip(plain, head):
        # the same work per request: what is generated moves into the prompt
        assert len(a.prompt) + a.max_new_tokens == \
            len(b.prompt) + b.max_new_tokens
        assert b.max_new_tokens >= 1
        assert b.greedy == (b.uid % traffic_lib.GREEDY_EVERY == 0)
    assert any(b.max_new_tokens < a.max_new_tokens
               for a, b in zip(plain[:8], head[:8]))
    assert all(a.max_new_tokens == b.max_new_tokens
               for a, b in zip(plain[8:], head[8:]))


def test_decode_contexts_group_steps_by_token_time():
    records = {0: rec(0, 3, [0.1, 0.2, 0.3]), 1: rec(1, 5, [0.15, 0.2, 0.3])}
    steps = serve_cell.decode_contexts(records, 0.0, 0.25)
    assert steps == {0.2: [4, 6]}


def test_pick_sample_takes_the_longest_greedy_first():
    records = {i: rec(i, 3, [0.1] * n, greedy=g) for i, (n, g) in
               enumerate([(5, True), (9, False), (7, True), (0, True),
                          (2, True)])}
    sample = serve_cell.pick_sample(records, 3, {"min_tokens": 8,
                                                 "max_requests": 5})
    assert sample[0].req.uid == 2
    assert {r.req.uid for r in sample} <= {0, 2, 4}
    assert sum(len(r.tokens) for r in sample) >= 8


def _reader(name):
    return common.load_reader(name)


def test_decode_occupancy_and_mfu_offline():
    ctx = {"kind": "serve", "config": CFG, "peaks": PEAK, "num_slots": 4,
           "seconds": 2.0, "decode_steps": {0.1: [4, 6], 0.2: [5, 7, 9]}}
    assert _reader("decode_occupancy.offline")(ctx) == pytest.approx(
        100 * 5 / 8)
    least = sum(flops.roofline_seconds(*flops.decode_step(CFG, c), PEAK)[0]
                for c in ctx["decode_steps"].values())
    assert _reader("mfu.offline")(ctx) == pytest.approx(100 * least / 2.0)
    assert _reader("decode_occupancy.offline")(
        dict(ctx, decode_steps={})) is None
    assert _reader("mfu.train")(dict(ctx, kind="serve")) is None


def _reduced(ops):
    return trace.Reduced(window_s=2.0, busy_s=1.5,
                         op_seconds={k: s for k, s, _ in ops},
                         op_text={k: k for k, _, _ in ops},
                         op_count={k: n for k, _, n in ops},
                         idle_gaps=[])


def test_kernel_rooflines_and_idle():
    att, head = "closed_call.22 = bf16[4]", "_unknown_.1 = s32[4,1]"
    r = _reduced([(att, 0.5, 8), (head, 0.25, 4), ("fusion.1 = f32[2]", 0.75, 4)])
    steps = {0.1: [4, 6], 0.2: [5, 7]}
    ctx = {"kind": "serve", "config": CFG, "peaks": PEAK, "num_slots": 4,
           "reduced": r, "traced_decode_steps": steps,
           "kernels": {att: "_paged_decode_kernel", head: "_head_kernel"}}
    # 8 attention calls at 2 layers a step are 4 steps
    mean = sum(flops.roofline_seconds(*flops.paged_attention(CFG, c),
                                      PEAK)[0] for c in steps.values()) / 2
    assert _reader("paged_decode_attention_roofline.offline")(ctx) == \
        pytest.approx(100 * mean * 4 / 0.5)
    one = flops.roofline_seconds(*flops.lm_head(CFG, 4), PEAK)[0]
    assert _reader("fused_lm_head_roofline.offline")(ctx) == pytest.approx(
        100 * one * 4 / 0.25)
    assert _reader("device_idle.offline")(ctx) == pytest.approx(25.0)
    # a kernel the compiled step does not name: nothing to read
    assert _reader("fused_lm_head_roofline.offline")(
        dict(ctx, kernels={att: "_paged_decode_kernel"})) is None


def test_train_readers():
    hlo = (BENCH / "testdata" / "small.hlo.txt").read_text()
    r = _reduced([("fusion = bf16[256]", 0.3, 3), ("copy-done = bf16[2]", 0.1, 3)])
    cfg = {"hidden_size": 8, "num_hidden_layers": 2, "num_attention_heads": 2,
           "intermediate_size": 16, "vocab_size": 10, "padded_vocab_size": 16,
           "gated_mlp": False, "mlm_transform": True}
    ctx = {"kind": "train", "config": cfg, "peaks": PEAK,
           "device": {"count": 1}, "reduced": r, "hlo_text": hlo,
           "traced_steps": 10, "tokens_per_step": 64, "seq_len": 8}
    assert _reader("gemm_share.train")(ctx) == pytest.approx(75.0)
    assert _reader("device_idle.train")(ctx) == pytest.approx(25.0)
    rate = 10 * 64 / 2.0
    assert _reader("mfu.train")(ctx) == pytest.approx(
        100 * flops.train_flops_per_token(cfg, 8) * rate / 197e12)
