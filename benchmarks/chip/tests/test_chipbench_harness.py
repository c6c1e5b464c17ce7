"""The harness around the cells: the result line's format, the refusal to
run without a TPU or without the program, and that a cell and a per-layer
metric are added by adding files and entries alone."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))

import run as run_mod  # noqa: E402
from lib import common  # noqa: E402
from lib.common import Check, RunResult  # noqa: E402


CELL = "internlm2-1.8b.offline-decode"


def _result(**kw):
    r = RunResult(end_to_end={"output_tokens_per_s": 136.5,
                              "itl_p95_ms": 120.25, "setup_s": 30.25},
                  checks=[Check("loss_gap", 0.001, 0.01),
                          Check("grad_gap", 0.5, 0.1)],
                  attempted=120, failed=0, notes=[], **kw)
    r.layer_ctx = {"memory_peak_bytes": 123, "kind": "serve", "num_slots": 16,
                   "config": common.find_cell(CELL).config}
    return r


def test_result_line_format():
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 123}
    line = run_mod.result_line(CELL, _result(), dev,
                               False, common.benchmark())
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is False          # grad_gap is over its limit
    assert line["metrics"] == {
        "output_tokens_per_s": {"value": 136.5, "unit": "tokens/s"},
        "itl_p95_ms": {"value": 120.25, "unit": "ms"},
        "setup_s": {"value": 30.25, "unit": "s"}}
    assert line["checks"]["grad_gap"] == {"value": 0.5, "limit": 0.1}
    json.loads(json.dumps(line))


def test_traced_line_has_device_times_and_breakdown(monkeypatch):
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
           "memory_peak_bytes": 123}
    r = _result(breakdown={"device_ops": [["fusion.1", 0.5]],
                           "idle_gaps": [["bench.step_call", 0.01]]},
                busy_s=2.5, window_s=3.0)
    line = run_mod.result_line(CELL, r, dev, True,
                               common.benchmark())
    assert list(line)[-2:] == ["breakdown", "checks"]
    assert line["device"]["busy_s"] == 2.5
    assert line["device"]["window_s"] == 3.0
    # no trace reduction in the context: every reader finds nothing
    assert line["metrics"] == {}


def _run(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "benchmarks/chip/run.py"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_no_result():
    p = _run(["--workload", CELL, "--seed",
              str(2 ** 40 + 3), "--seconds", "1"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out",
                                                  "__pycache__"))
    p = _run(["--workload", CELL, "--seed", "1",
              "--seconds", "1"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_new_cell_and_metric_are_files_and_entries(tmp_path):
    """A later change adds a traffic mix and a per-layer reader as new files
    and entries; nothing that is there is edited."""
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".jax_cache", ".out",
                                                  "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "internlm2-1.8b.dummy-mix", "config": "internlm2-1.8b",
        "traffic": "dummy-mix", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "dummy_share.train", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "model step",
        "moves": "output_tokens_per_s",
        "workloads": ["internlm2-1.8b.dummy-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    chip = tmp_path / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    (chip / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "train", "batch": 2, "seq_len": 8}))
    (chip / "metrics" / "dummy_share.train.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx['kind'] == 'train' else None\n")
    assert all(p.read_bytes() == b for p, b in before.items())
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(chip)!r})
        from lib import common
        cell = common.find_cell("internlm2-1.8b.dummy-mix")
        assert cell.traffic == {{"kind": "train", "batch": 2, "seq_len": 8}}
        assert cell.config["hidden_size"] == 2048
        names = [m["name"] for m in common.metrics_for(cell.name, "per_layer")]
        assert names == ["dummy_share.train"], names
        assert common.load_reader("dummy_share.train")({{"kind": "train"}}) == 42.0
        e2e = [m["name"] for m in common.metrics_for(cell.name, "end_to_end")]
        assert e2e == ["setup_s"], e2e
        print("ok")
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"
