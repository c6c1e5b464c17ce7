"""HLO cost engine: trip-count multiplication, dot pricing, collective parse —
validated against XLA cost_analysis on unrolled graphs and known-flop programs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import characterize, hlotext


def _cost(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return characterize.analyze_text(c.as_text(), 1), c


def _xla_flops(compiled):
    return compiled.cost_analysis()["flops"]


def test_dot_flops_exact():
    a = jnp.zeros((64, 128), jnp.float32)
    b = jnp.zeros((128, 256), jnp.float32)
    cost, compiled = _cost(lambda x, y: x @ y, a, b)
    expected = 2 * 64 * 128 * 256
    assert abs(cost.flops - expected) / expected < 0.01
    xla = _xla_flops(compiled)
    assert abs(cost.flops - xla) / expected < 0.05


def test_scan_trip_count_multiplication():
    """XLA counts while bodies once; the engine multiplies by trip count."""
    x = jnp.zeros((32, 64), jnp.float32)
    ws = jnp.zeros((24, 64, 64), jnp.float32)

    def f(x, ws):
        return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

    cost, compiled = _cost(f, x, ws)
    expected = 24 * 2 * 32 * 64 * 64
    assert abs(cost.flops - expected) / expected < 0.05
    assert _xla_flops(compiled) < expected / 5  # body-once


def test_scan_matches_unrolled():
    x = jnp.zeros((16, 32), jnp.float32)
    ws = jnp.zeros((8, 32, 32), jnp.float32)

    def scanned(x, ws):
        return jax.lax.scan(lambda c, w: (jnp.tanh(c @ w), None), x, ws)[0]

    def unrolled(x, ws):
        for i in range(8):
            x = jnp.tanh(x @ ws[i])
        return x

    c1, _ = _cost(scanned, x, ws)
    c2, _ = _cost(unrolled, x, ws)
    assert abs(c1.flops - c2.flops) / c2.flops < 0.05


def test_collective_parsing():
    line = ("%all-reduce.1 = f32[64,1024]{1,0} all-reduce(%dot), channel_id=1, "
            "replica_groups=[4,4]<=[16], use_global_device_ids=true")
    table = {"dot": "f32[64,1024]{1,0}"}
    summary = hlotext.parse_collectives(
        "%dot = f32[64,1024]{1,0} parameter(0)\n" + line, 16)
    assert len(summary.ops) == 1
    op = summary.ops[0]
    assert op.kind == "all-reduce" and op.group_size == 4
    assert op.result_bytes == 64 * 1024 * 4
    # ring all-reduce wire bytes: 2*(g-1)/g * operand
    assert abs(op.wire_bytes - 2 * 3 / 4 * op.operand_bytes) < 1.0


def test_shape_bytes():
    assert hlotext.shape_bytes("f32[8,4]{1,0}") == 128
    assert hlotext.shape_bytes("bf16[10]") == 20
    assert hlotext.shape_bytes("(f32[2,2], s8[4])") == 20


def test_scope_bucketing():
    buckets = characterize.bucket_scopes({
        "jit(step)/lamb/mul": 10.0,
        "jit(step)/while/body/mlp/dot_general": 5.0,
        "jit(step)/while/attn_core/exp": 2.0,
        "unknown_thing": 1.0,
    })
    assert buckets["lamb"] == 10.0
    assert buckets["mlp"] == 5.0
    assert buckets["attn_bgemm"] == 2.0
    assert buckets["other"] == 1.0
