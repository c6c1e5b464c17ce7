"""The trace reduction, on a small trace recorded on a TPU v5e (three calls
of one jitted matmul+tanh, each inside the benchmark's host spans) and on
the compiled HLO text of that program."""
import base64
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from lib import trace  # noqa: E402

DATA = BENCH / "testdata"


@pytest.fixture(scope="module")
def small():
    return trace.load(DATA / "small.xplane.pb")


def test_planes_and_spans(small):
    assert list(small.devices) == ["/device:TPU:0"]
    assert [s.name for s in small.spans] == ["bench.step_call",
                                             "bench.sync"] * 3
    assert len(trace.ops(small, "/device:TPU:0")) == 9


def test_reduce_window_busy_and_ops(small):
    r = trace.reduce(small)
    # first span start 46561120 ns, last span end 53461980 + 441960 ns
    assert r.window_s == pytest.approx((53461980 + 441960 - 46561120) / 1e9,
                                       abs=1e-15)
    # the device ops inside the window: two calls of copy-start (13 ns),
    # copy-done (2 ns, 3 ns) and the fusion (2088 ns, 1843 ns)
    assert r.busy_s == pytest.approx((13 + 2 + 2088 + 13 + 3 + 1843) / 1e9,
                                     abs=1e-15)
    fusion = "fusion = bf16[256]{0:T(256)(128)(2,1)}"
    assert r.op_seconds[fusion] == pytest.approx((2088 + 1843) / 1e9,
                                                 abs=1e-15)
    assert r.op_count[fusion] == 2
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s, abs=1e-12)
    assert {n for n, _ in r.idle_gaps} <= {"bench.sync", "bench.step_call",
                                           "host:between-spans"}
    bd = r.breakdown()
    assert bd["device_ops"][0] == [fusion, r.op_seconds[fusion]]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [
        (1, 4), (5, 10)]
    assert trace.union([(0, 1)], 2, 3) == []


def test_op_key_and_control_flow():
    ev = ("%_unknown_.1 = (s32[16,1]{1,0:T(8,128)S(1)}, s32[16,1]{1,0:T(8,128)})"
          " custom-call(bf16[16,2048]{1,0} %fusion.57)")
    assert trace.op_key(ev) == ("_unknown_.1 = (s32[16,1]{1,0:T(8,128)S(1)}, "
                                "s32[16,1]{1,0:T(8,128)})")
    line = ("  ROOT %_unknown_.1 = (s32[16,1]{1,0:T(8,128)S(1)}, "
            "s32[16,1]{1,0:T(8,128)}) custom-call(%fusion.57)")
    assert trace.op_key(line) == trace.op_key(ev)
    assert trace.is_control("%while.7 = (s32[], bf16[2]) while(%t), body=%b")
    assert not trace.is_control("%fusion.3 = bf16[2] fusion(%p), calls=%c")


def test_gemm_ops_from_hlo_text():
    assert trace.gemm_ops((DATA / "small.hlo.txt").read_text()) == {
        "convolution.3", "fusion"}


def test_kernel_names_from_mosaic_payload():
    body = base64.b64encode(b"\x00MLIR\x00_paged_decode_kernel\x00x").decode()
    hlo = ('  %closed_call.22 = bf16[16,8,2,128]{3,2,1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call", backend_config={"custom_'
           f'call_config":{{"body":"{body}"}}}}\n'
           '  %fusion.1 = bf16[2]{0} fusion(%b), kind=kLoop, calls=%c\n')
    assert trace.kernel_names(hlo) == {
        "closed_call.22 = bf16[16,8,2,128]{3,2,1,0}": "_paged_decode_kernel"}
