"""tools/check_bench.py: the CI benchmark regression gate's compare logic.

Pure-dict tests (no benchmark run): regressions beyond tolerance fail,
improvements and in-tolerance noise pass, and a *partial* artifact — a
baseline metric missing from the current result — fails rather than being
skipped, which is the whole point of gating the upload.
"""
import copy
import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _load_check_bench():
    spec = importlib.util.spec_from_file_location(
        "check_bench", ROOT / "tools" / "check_bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cb = _load_check_bench()

BASELINE = {
    "rates": {
        "4": {"continuous": {"tok_s": 100.0}, "static": {"tok_s": 50.0}},
        "inf": {"continuous": {"tok_s": 200.0}},
    },
    "shared_prefix": {
        "off": {"tok_s": 60.0, "ttft_ms": 1000.0},
        "on": {"tok_s": 80.0, "ttft_ms": 700.0},
    },
    "sampled": {"greedy": {"tok_s": 150.0}, "sampled": {"tok_s": 120.0},
                "sampler_overhead_pct": 25.0,
                "diverged_requests": 8},
    "families": {
        "mamba2-1.3b": {"tok_s": 40.0, "prefix_cache": "off: ssm"},
        "jamba-v0.1-52b": {"tok_s": 20.0, "prefix_cache": "off: ssm"},
        "deepseek-moe-16b": {"tok_s": 30.0, "prefix_cache": "on"},
    },
    "recompiles": {"engines": 12, "variants": 40, "traces": 40, "excess": 0},
    "multistep": {
        "n1": {"tok_s": 300.0, "dispatches_per_token": 0.30},
        "n4": {"tok_s": 350.0, "dispatches_per_token": 0.09,
               "speedup_vs_n1": 1.17},
        "n16": {"tok_s": 380.0, "dispatches_per_token": 0.04,
                "speedup_vs_n1": 1.27},
        "diverged_streams": 0,
    },
}


def _failed(rows):
    return [r["metric"] for r in rows if not r["ok"]]


def test_identical_results_pass():
    assert _failed(cb.compare(copy.deepcopy(BASELINE), BASELINE, 0.2)) == []


def test_metric_inventory_matches_baseline_sections():
    paths = [m[0] for m in cb.iter_metrics(BASELINE)]
    assert "rates.4.continuous.tok_s" in paths
    assert "rates.inf.continuous.tok_s" in paths
    assert "shared_prefix.on.ttft_ms" in paths
    assert "sampled.sampled.tok_s" in paths
    assert "sampled.sampler_overhead_pct" in paths
    assert "families.jamba-v0.1-52b.tok_s" in paths
    assert "multistep.n4.tok_s" in paths
    assert "multistep.n16.dispatches_per_token" in paths
    assert "multistep.n4.speedup_vs_n1" in paths
    assert "multistep.diverged_streams" in paths
    # static engine numbers are context, not gated; so is how many sampled
    # streams differ from greedy
    assert not any("static" in p for p in paths)
    assert "sampled.diverged_requests" not in paths


def test_baseline_without_families_section_fails():
    """`families` is a REQUIRED baseline section: a baseline that predates
    the hybrid/SSM/MoE serving sweep would silently un-gate it — the gate
    must demand a re-baseline instead."""
    old = {k: v for k, v in copy.deepcopy(BASELINE).items()
           if k != "families"}
    rows = cb.compare(copy.deepcopy(old), old, 0.2)
    missing = [r for r in rows if not r["ok"]]
    assert [r["metric"] for r in missing] == ["families.<section>"]
    assert "re-baseline" in missing[0]["note"]


def test_families_regression_and_partial_artifact_fail():
    cur = copy.deepcopy(BASELINE)
    cur["families"]["mamba2-1.3b"]["tok_s"] = 40.0 * 0.5       # -50%
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == \
        ["families.mamba2-1.3b.tok_s"]
    cur = {k: v for k, v in copy.deepcopy(BASELINE).items()
           if k != "families"}
    rows = cb.compare(cur, BASELINE, 0.2)
    assert all("MISSING" in r["note"] for r in rows if not r["ok"])
    assert {r["metric"] for r in rows if not r["ok"]} == {
        "families.mamba2-1.3b.tok_s", "families.jamba-v0.1-52b.tok_s",
        "families.deepseek-moe-16b.tok_s"}


def test_recompile_excess_gated_at_exactly_zero():
    """``recompiles.excess`` uses direction "zero": ONE retrace fails the
    gate no matter how loose the tolerance — a recompile after warmup is a
    correctness bug, not a perf number tolerance should forgive."""
    cur = copy.deepcopy(BASELINE)
    cur["recompiles"]["excess"] = 1
    rows = cb.compare(cur, BASELINE, tolerance=10.0)
    assert _failed(rows) == ["recompiles.excess"]
    assert "correctness invariant" in \
        [r for r in rows if r["metric"] == "recompiles.excess"][0]["note"]


def test_baseline_without_recompiles_section_fails():
    old = {k: v for k, v in copy.deepcopy(BASELINE).items()
           if k != "recompiles"}
    rows = cb.compare(copy.deepcopy(old), old, 0.2)
    missing = [r for r in rows if not r["ok"]]
    assert [r["metric"] for r in missing] == ["recompiles.<section>"]
    assert "re-baseline" in missing[0]["note"]


def test_baseline_without_sampled_section_fails():
    """`sampled` is REQUIRED: a baseline predating it would silently drop
    the sampler-overhead coverage."""
    old = {k: v for k, v in copy.deepcopy(BASELINE).items()
           if k != "sampled"}
    rows = cb.compare(copy.deepcopy(old), old, 0.2)
    missing = [r for r in rows if not r["ok"]]
    assert [r["metric"] for r in missing] == ["sampled.<section>"]


def test_baseline_without_multistep_section_fails():
    """`multistep` became REQUIRED with the compiled decode loop: a baseline
    predating it would silently drop the dispatch-bound and N-vs-1 stream
    divergence coverage."""
    old = {k: v for k, v in copy.deepcopy(BASELINE).items()
           if k != "multistep"}
    rows = cb.compare(copy.deepcopy(old), old, 0.2)
    missing = [r for r in rows if not r["ok"]]
    assert [r["metric"] for r in missing] == ["multistep.<section>"]
    assert "re-baseline" in missing[0]["note"]


def test_multistep_gate_directions():
    """dispatches_per_token regressing UP (more host syncs per token) fails;
    dropping further passes. One N>1-vs-N=1 token mismatch fails at any
    tolerance — the loop's whole contract is stream invisibility."""
    cur = copy.deepcopy(BASELINE)
    cur["multistep"]["n4"]["dispatches_per_token"] = 0.09 * 1.5
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == \
        ["multistep.n4.dispatches_per_token"]
    cur["multistep"]["n4"]["dispatches_per_token"] = 0.09 * 0.5
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == []
    cur["multistep"]["diverged_streams"] = 1
    assert _failed(cb.compare(cur, BASELINE, tolerance=10.0)) == \
        ["multistep.diverged_streams"]


def test_sampler_overhead_gated_in_absolute_points():
    """``sampler_overhead_pct`` uses direction "lower_points": the current
    overhead may exceed the baseline by at most 100 * tolerance percentage
    points. A relative bound would flap once the baseline is a small
    percentage (25% * 1.2 = 30% leaves 5 points of room; 25 + 20 = 45
    points is the intended slack)."""
    cur = copy.deepcopy(BASELINE)
    cur["sampled"]["sampler_overhead_pct"] = 44.0      # +19pp < 20pp slack
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == []
    cur["sampled"]["sampler_overhead_pct"] = 46.0      # +21pp > 20pp slack
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == \
        ["sampled.sampler_overhead_pct"]
    # an improvement always passes
    cur["sampled"]["sampler_overhead_pct"] = 1.0
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == []


def test_throughput_regression_beyond_tolerance_fails():
    cur = copy.deepcopy(BASELINE)
    cur["rates"]["inf"]["continuous"]["tok_s"] = 200.0 * 0.7   # -30%
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == \
        ["rates.inf.continuous.tok_s"]


def test_ttft_direction_is_inverted():
    cur = copy.deepcopy(BASELINE)
    cur["shared_prefix"]["on"]["ttft_ms"] = 700.0 * 1.5        # slower TTFT
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == \
        ["shared_prefix.on.ttft_ms"]
    # a FASTER TTFT (lower) of the same magnitude passes
    cur["shared_prefix"]["on"]["ttft_ms"] = 700.0 * 0.5
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == []


def test_within_tolerance_noise_passes():
    cur = copy.deepcopy(BASELINE)
    cur["rates"]["4"]["continuous"]["tok_s"] = 100.0 * 0.85    # -15% < 20%
    cur["shared_prefix"]["off"]["ttft_ms"] = 1000.0 * 1.1
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == []


def test_partial_artifact_fails_not_skips():
    cur = {k: v for k, v in copy.deepcopy(BASELINE).items()
           if k != "sampled"}
    rows = cb.compare(cur, BASELINE, 0.2)
    missing = [r for r in rows if not r["ok"]]
    assert {r["metric"] for r in missing} == \
        {"sampled.greedy.tok_s", "sampled.sampled.tok_s",
         "sampled.sampler_overhead_pct"}
    assert all("MISSING" in r["note"] for r in missing)


def test_extra_current_sections_are_ignored():
    cur = copy.deepcopy(BASELINE)
    cur["tensor_parallel"] = {"tp": 2, "diverged_streams": 0}
    assert _failed(cb.compare(cur, BASELINE, 0.2)) == []


def test_empty_baseline_fails_loudly():
    rows = cb.compare({}, {}, 0.2)
    assert _failed(rows) == ["<none>"]


def test_cli_exit_codes(tmp_path):
    import json
    base = tmp_path / "base.json"
    cur = tmp_path / "cur.json"
    base.write_text(json.dumps(BASELINE))
    cur.write_text(json.dumps(BASELINE))
    assert cb.main([str(cur), str(base)]) == 0
    bad = copy.deepcopy(BASELINE)
    bad["sampled"]["greedy"]["tok_s"] = 1.0
    cur.write_text(json.dumps(bad))
    assert cb.main([str(cur), str(base)]) == 1
    # committed baseline must itself pass the gate's schema
    rows = cb.compare(
        json.loads((ROOT / "benchmarks" / "baselines" /
                    "serving.json").read_text()),
        json.loads((ROOT / "benchmarks" / "baselines" /
                    "serving.json").read_text()), 0.2)
    assert _failed(rows) == []
