"""Static recompilation auditor for the continuous serving engine.

The engine's jit cache is *lazy per variant* (decode/prefill × sampled ×
filtered × final): each variant compiles once, on the first traffic that
needs it, and the whole serving design rests on the cache then being
**closed** — fixed batch shapes, fixed chunk shapes, static flags — so
steps 2..N of any trace add zero new traces. That closure is also exactly
what the lazy cache can silently mask: a shape or weak-type leak into a
traced signature (a python int where an array belonged, a page table that
changed width) retraces *the same variant* every step, which perf tests
read as "mysteriously slow" rather than "broken".

This auditor proves closure statically. :class:`AuditEngine` replaces the
engine's ``_build`` step compiler with a recorder that **abstract-evals**
(``jax.eval_shape`` — no device execution, no kernels, no FLOPs) each call
and logs its abstract signature under the variant's jit-cache key. Running
a representative mixed trace (greedy + sampled + filtered traffic, shared
prefixes, a starved page pool forcing growth and preemption replay) then
asserts every exercised variant saw exactly ONE signature. A planted
retrace — e.g. mutating the chunk size mid-trace — fails loudly
(``tests/test_recompile_audit.py`` seeds exactly that).

Coverage matrix (``python -m repro.analysis.recompile`` runs all of it; the
tests pin representative cells):

    every servable family   × tp ∈ {1, ..devices}  × N=1
    every servable family   × tp=1                 × N=4
    dense                   × tp ∈ {2, ..devices}  × N=4

The N=4 rows audit the multi-step compiled decode loop: its decode keys
gain the horizon element (``("decode", sampled, filtered, N)``)
and the per-dispatch predicate arrays (active mask, budgets, page capacity,
EOS ids) must not perturb the traced signature. tp > 1 audits shard-map
the abstract step over a real device mesh, so they need
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the tests run them
in a subprocess; the CLI audits every tp the visible device count
supports).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import smoke_config
from ..models import build_model
from ..parallel import sharding as shardlib
from ..serving.engine import SERVABLE_FAMILIES, ContinuousEngine
from ..serving.sampling import SamplingParams
from ..serving.scheduler import Request

# the smoke-sized representative of each servable family
FAMILY_ARCHS: Dict[str, str] = {
    "dense": "llama3.2-3b",
    "moe": "deepseek-moe-16b",
    "vlm": "qwen2-vl-2b",
    "ssm": "mamba2-1.3b",
    "hybrid": "jamba-v0.1-52b",
}
assert set(FAMILY_ARCHS) == set(SERVABLE_FAMILIES)


class AuditError(AssertionError):
    """The jit cache is not closed: a variant traced more than once."""


def _abstract(leaf) -> Tuple:
    """The part of a leaf that decides whether jit re-traces: shape, dtype,
    weak-typedness. A python scalar slipping in where an array belonged
    shows up here as a distinct (weak) signature."""
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return (tuple(leaf.shape), str(leaf.dtype),
                bool(getattr(leaf, "weak_type", False)))
    return ("pyval", type(leaf).__name__, repr(leaf))


class _Recorder:
    """Stands in for one compiled step variant: abstract-evals on each new
    signature, replays cached zero outputs otherwise. The zero token stream
    keeps the host scheduler honest (stop checks, slot recycling, page
    growth all run for real); only the model math is skipped."""

    def __init__(self, engine: "AuditEngine", impl, key: Tuple):
        self.engine = engine
        self.impl = impl
        self.key = key
        self._outs: Dict[Tuple, Any] = {}

    def __call__(self, *args):
        leaves = jax.tree_util.tree_leaves(args)
        sig = tuple(_abstract(leaf) for leaf in leaves)
        sigs = self.engine.signatures.setdefault(self.key, [])
        if sig not in sigs:
            sigs.append(sig)
            out_shapes = jax.eval_shape(self.impl, *args)
            self._outs[sig] = jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shapes)
        return self._outs[sig]


class AuditEngine(ContinuousEngine):
    """A ContinuousEngine whose steps never execute: ``_build`` hands back a
    signature recorder instead of a jit-compiled function. Everything else —
    scheduler, allocator, prefix index, chunking, preemption — runs the real
    host code against the zero token stream."""

    def __init__(self, model, params, **kw):
        # the sanitizer's device-side probes would read the recorder's
        # all-zeros output as "non-finite check failed: False" — the audit
        # is abstract by construction, so force it off
        kw["sanitize"] = False
        super().__init__(model, params, **kw)
        # jit-cache key -> ordered distinct abstract signatures
        self.signatures: Dict[Tuple, List[Tuple]] = {}

    def _build(self, impl, in_specs, out_specs, donate, key=()):
        if self.mesh is not None:
            impl = shardlib.shard_map_tp(impl, self.mesh, in_specs, out_specs)
        return _Recorder(self, impl, key)


@dataclasses.dataclass
class AuditReport:
    """Signature census of one audited trace: family, tp, and per-variant
    distinct-signature counts."""
    family: str
    arch: str
    tp: int
    signatures: Dict[Tuple, List[Tuple]]

    @property
    def variants(self) -> List[Tuple]:
        return sorted(self.signatures)

    def check(self) -> "AuditReport":
        """Raise AuditError unless every variant has exactly one trace."""
        if not self.signatures:
            raise AuditError(
                f"[{self.family}/tp={self.tp}] trace exercised no engine "
                "step at all — the audit traffic is broken")
        open_keys = {k: len(v) for k, v in self.signatures.items()
                     if len(v) != 1}
        if open_keys:
            detail = "; ".join(
                f"{k}: {n} distinct signatures" for k, n in
                sorted(open_keys.items(), key=lambda kv: str(kv[0])))
            raise AuditError(
                f"[{self.family}/tp={self.tp}] jit cache not closed — a "
                f"variant re-traced after its first call: {detail}")
        return self

    def summary(self) -> str:
        keys = ", ".join(str(k) for k in self.variants)
        return (f"{self.family:<7} ({self.arch}) tp={self.tp}: "
                f"{len(self.signatures)} variant(s), 1 trace each [{keys}]")


def _audit_requests(vocab: int, seed: int = 0) -> List[Request]:
    """Mixed traffic that exercises every step variant the engine can lazily
    build: greedy, sampled-unfiltered, sampled-filtered; a shared prefix
    (prefix cache + CoW tail where supported); prompt lengths spanning
    multiple chunks; generation lengths that outgrow pages."""
    rng = np.random.default_rng(seed)
    shared = list(map(int, rng.integers(5, vocab, 10)))
    reqs = []
    for i in range(7):
        if i < 3:
            # shared 10-token prefix: 2 full pages + a partial tail, so the
            # second/third admissions exercise prefix sharing and CoW
            prompt = shared + list(map(int, rng.integers(
                5, vocab, int(rng.integers(2, 6)))))
        elif i == 3:
            # longer than one prefill chunk: non-final chunk variant
            prompt = list(map(int, rng.integers(5, vocab, 22)))
        else:
            prompt = list(map(int, rng.integers(
                5, vocab, int(rng.integers(4, 14)))))
        sp = (SamplingParams(),                                   # greedy
              SamplingParams(temperature=0.8, seed=10 + i),       # sampled
              SamplingParams(temperature=0.9, top_k=8, top_p=0.9,
                             seed=20 + i))[i % 3]                 # filtered
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(4, 10)),
                            sampling=sp))
    return reqs


def audit_family(family: str, *, tp: int = 1, decode_steps: int = 1,
                 requests: Optional[Sequence[Request]] = None) -> AuditReport:
    """Abstract-serve one family's smoke arch and assert cache closure.

    The pool is deliberately starved (2 slots, 12 pages) so the trace also
    covers page growth, prefix eviction, CoW tail copies, and forced-replay
    preemption — the paths where a retrace bug would hide behind rare
    traffic. ``decode_steps > 1`` audits the multi-step compiled decode
    loop's variants instead (decode keys gain the horizon element; the
    per-dispatch predicate arrays must not perturb the traced signature)."""
    arch_name = FAMILY_ARCHS[family]
    arch = smoke_config(arch_name)
    if tp > 1 and arch.num_kv_heads % tp and tp % arch.num_kv_heads:
        arch = dataclasses.replace(arch, num_kv_heads=tp)
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    engine = AuditEngine(model, params, num_slots=2, num_pages=12,
                         page_size=4, max_seq_len=40, tp=tp,
                         decode_steps=decode_steps)
    reqs = list(requests) if requests is not None \
        else _audit_requests(arch.vocab_size)
    results = engine.run(reqs)
    assert all("tokens" in r for r in results.values())
    return AuditReport(family=family, arch=arch_name, tp=tp,
                       signatures=dict(engine.signatures)).check()


def audit_all(tps: Sequence[int] = (1,),
              families: Sequence[str] = SERVABLE_FAMILIES
              ) -> List[AuditReport]:
    return [audit_family(f, tp=tp) for tp in tps for f in families]


def main() -> int:
    tps = [1]
    if jax.device_count() >= 2:
        tps.append(2)
    print(f"[recompile-audit] families={list(SERVABLE_FAMILIES)} tps={tps}")
    failed = 0
    # every family re-audits at decode_steps=4 so the multi-step compiled
    # decode loop's horizon-keyed variants prove closure too (dense also at
    # every tp the mesh supports)
    jobs = [(f, tp, 1) for tp in tps for f in SERVABLE_FAMILIES]
    jobs += [(f, 1, 4) for f in SERVABLE_FAMILIES]
    jobs += [("dense", tp, 4) for tp in tps if tp > 1]
    for family, tp, steps in jobs:
        try:
            report = audit_family(family, tp=tp, decode_steps=steps)
        except AuditError as e:
            failed += 1
            print(f"FAIL {e}")
        else:
            tag = f" [decode_steps={steps}]" if steps > 1 else ""
            print(f"ok   {report.summary()}{tag}")
    if failed:
        print(f"[recompile-audit] {failed} audit(s) FAILED — the jit cache "
              "is not closed; see signatures above")
        return 1
    print("[recompile-audit] all caches closed (steps 2..N add zero traces)")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
