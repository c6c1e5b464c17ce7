"""Recompilation auditor: closed jit caches for the servable families, a
planted shape-dependent retrace that must fail loudly, and the tp=2 audit
over a real (forced-host) device mesh.

The audits are abstract — ``jax.eval_shape`` only, no kernels execute — so
these tests are cheap despite covering full serving traces.
"""
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis.recompile import (FAMILY_ARCHS, AuditEngine, AuditError,
                                      AuditReport, audit_family)
from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import Request

ROOT = Path(__file__).resolve().parents[1]


def _run_subprocess(body: str):
    script = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n" + body)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, cwd=ROOT, timeout=500,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root"})
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    return r.stdout


# ------------------------------------------------------------ closed caches --

def test_dense_cache_closed_and_fully_exercised():
    report = audit_family("dense")      # .check() already ran inside
    kinds = {k[0] for k in report.variants}
    # the starved-pool mixed traffic must reach every step kind the dense
    # engine can build: both prefill finalities, all decode sampling
    # variants, and the CoW tail copy
    assert kinds == {"decode", "prefill", "copy"}
    assert ("prefill", False, False, False) in report.variants, \
        "non-final prefill chunk variant never exercised"
    # the keys hold the step kind and the static sampling flags, nothing
    # else: one decode path, one filter
    assert ("decode", True, True) in report.variants
    assert ("prefill", True, True, True) in report.variants
    assert {len(k) for k in report.variants if k[0] == "decode"} == {3}
    assert {len(k) for k in report.variants if k[0] == "prefill"} == {4}
    assert all(len(sigs) == 1 for sigs in report.signatures.values())


def test_fused_decode_multistep_cache_closed_all_families():
    """Every servable family: the multi-step loop's horizon-keyed variants
    (('decode', sampled, filtered, N)) stay closed."""
    for family in sorted(FAMILY_ARCHS):
        report = audit_family(family, decode_steps=4)
        assert ("decode", True, True, 4) in report.variants, \
            (family, report.variants)
        assert all(len(s) == 1 for s in report.signatures.values())


def test_hybrid_cache_closed():
    report = audit_family("hybrid")
    kinds = {k[0] for k in report.variants}
    # hybrid serves with the prefix cache gated off: no copy variant exists
    assert kinds == {"decode", "prefill"}
    assert all(len(sigs) == 1 for sigs in report.signatures.values())


def test_report_summary_names_every_variant():
    report = audit_family("moe")
    s = report.summary()
    assert "moe" in s and "tp=1" in s
    assert f"{len(report.signatures)} variant(s)" in s


# ---------------------------------------------------------- planted retrace --

def _greedy(uid, prompt, n=3):
    return Request(uid=uid, prompt=prompt, max_new_tokens=n)


def test_planted_shape_retrace_is_detected():
    """Mutate the prefill chunk size between traces: the same
    ('prefill', final, ...) variant now sees two chunk widths — exactly the
    silent-retrace bug class the auditor exists to catch."""
    arch = smoke_config(FAMILY_ARCHS["dense"])
    model = build_model(arch)
    engine = AuditEngine(model, model.init(__import__("jax").random.key(0)),
                         num_slots=2, num_pages=16, page_size=4,
                         max_seq_len=48)
    engine.run([_greedy(0, list(range(5, 15)))])        # one 16-wide chunk
    engine.prefill_chunk = 8                            # the planted bug
    engine.run([_greedy(1, list(range(30, 40)))])       # two 8-wide chunks
    report = AuditReport(family="dense", arch=FAMILY_ARCHS["dense"], tp=1,
                         signatures=dict(engine.signatures))
    with pytest.raises(AuditError, match="not closed"):
        report.check()
    # and the census pinpoints the culprit: the final-prefill variant holds
    # two distinct signatures, decode still one
    final_prefill = engine.signatures[("prefill", True, False, False)]
    assert len(final_prefill) == 2
    assert len(engine.signatures[("decode", False, False)]) == 1


def test_empty_trace_is_an_audit_failure():
    with pytest.raises(AuditError, match="no engine step"):
        AuditReport(family="dense", arch="x", tp=1, signatures={}).check()


# ------------------------------------------------------------------- tp = 2 --

def test_tp2_caches_closed_over_device_mesh():
    """tp=2 audits shard-map the abstract step over a real 2-device mesh, so
    they run in a subprocess with forced host devices (the pattern
    ``test_tp_serving.py`` established)."""
    out = _run_subprocess(r"""
from repro.analysis.recompile import audit_family
for family in ("dense", "hybrid"):
    report = audit_family(family, tp=2)
    print("closed", family, len(report.signatures))
print("AUDIT_TP2_OK")
""")
    assert "AUDIT_TP2_OK" in out
    assert out.count("closed") == 2
