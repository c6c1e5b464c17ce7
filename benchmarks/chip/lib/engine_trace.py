"""The serving engine's own spans and programs in a traced run's profiler
trace, for the per-layer readers of the host loop and the decode step.

The engine records one ``engine.step`` span per loop iteration on the
host's ``python`` line. Inside it, never overlapping, are its phases:
``engine.admit`` (arg ``uid``), ``engine.prefill`` (``uid``, ``tokens``,
``final``), ``engine.prefill_wait``, ``engine.schedule``, ``engine.decode``
(``slots``: sequences decoded; ``kv_tokens``: the tokens they hold in the
cache, which the step reads; ``horizon``), ``engine.decode_wait`` and
``engine.emit``. Its programs compile as ``jit_serve_decode_step``,
``jit_serve_decode_loop``, ``jit_serve_prefill_chunk`` and
``jit_serve_copy_page``; each run of one is an event of that name (with
its fingerprint in brackets) on each chip's ``XLA Modules`` line, on the
same clock as the host's spans.

A program without these spans and names (one older than them) leaves every
reader here with nothing to read: each returns None.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import statistics
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from . import common, flops
from .trace import SPAN_PREFIX, Event

ENGINE_PREFIX = "engine."
DECODE_STEP = "jit_serve_decode_step"
DECODE_LOOP = "jit_serve_decode_loop"
# the host blocked on the device, inside an engine.step
WAITS = ("engine.decode_wait", "engine.prefill_wait")


@dataclasses.dataclass
class Span(Event):
    args: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class EngineTrace:
    window: Tuple[int, int]         # the benchmark's spans' extent, ns
    spans: List[Span]               # engine.* spans, by start
    modules: List[Event]            # XLA Modules events of every chip

    def inside(self, events):
        lo, hi = self.window
        return [e for e in events if e.start >= lo and e.end <= hi]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.inside(self.spans) if s.name == name]

    def program(self, module: str) -> List[Event]:
        """Runs of one program in the window: its events are named
        ``<module>(<fingerprint>)``."""
        return [e for e in self.inside(self.modules)
                if e.name.split("(", 1)[0] == module]


def load(path: Path) -> EngineTrace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    bench: List[Event] = []
    spans: List[Span] = []
    modules: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [Event(e.name, int(e.start_ns),
                                      int(e.duration_ns))
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        bench.append(Event(e.name, int(e.start_ns),
                                           int(e.duration_ns)))
                    elif e.name.startswith(ENGINE_PREFIX):
                        spans.append(Span(
                            e.name, int(e.start_ns), int(e.duration_ns),
                            {k: v for k, v in e.stats
                             if isinstance(v, int)}))
    window = (min((s.start for s in bench), default=0),
              max((s.end for s in bench), default=0))
    spans.sort(key=lambda s: s.start)
    return EngineTrace(window=window, spans=spans, modules=modules)


def latest_xplane(directory: Path) -> Optional[Path]:
    found = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    return Path(max(found, key=os.path.getmtime)) if found else None


@functools.lru_cache(maxsize=1)
def _load_cached(path: str, mtime_ns: int) -> EngineTrace:
    return load(Path(path))


def for_run(ctx: dict) -> Optional[EngineTrace]:
    """The trace a traced serving run just wrote under
    ``common.OUT_DIR / "trace"``, loaded once for all the readers; None
    for a run that took no trace."""
    if ctx.get("kind") != "serve" or ctx.get("reduced") is None:
        return None
    path = latest_xplane(common.OUT_DIR / "trace")
    if path is None:
        return None
    return _load_cached(str(path), path.stat().st_mtime_ns)


def host_ms_per_step(et: EngineTrace) -> Optional[float]:
    """Median, over the ``engine.step`` spans in the window that hold an
    ``engine.decode``, of the step's duration less its ``engine.decode_wait``
    and ``engine.prefill_wait`` spans: the host's serial time per decode
    step, in ms."""
    inner = [s for s in et.inside(et.spans) if s.name != "engine.step"]
    serial = []
    for step in et.named("engine.step"):
        kids = [s for s in inner
                if s.start >= step.start and s.end <= step.end]
        if any(s.name == "engine.decode" for s in kids):
            serial.append(step.dur - sum(s.dur for s in kids
                                         if s.name in WAITS))
    return statistics.median(serial) / 1e6 if serial else None


def decode_step_ms(et: EngineTrace) -> Optional[float]:
    """Median device time of one decode dispatch in the window (the
    single-step program, or the multi-step loop), in ms."""
    runs = et.program(DECODE_STEP) + et.program(DECODE_LOOP)
    return statistics.median(e.dur for e in runs) / 1e6 if runs else None


def decode_step_roofline(et: EngineTrace, cfg: dict, peaks: dict
                         ) -> Optional[float]:
    """100 x the least time of the window's single decode steps over their
    device time: per ``engine.decode`` span the larger of its FLOPs over the
    bf16 peak and its bytes over the HBM bandwidth (``flops.decode_step``;
    each of ``slots`` sequences attends to its ``kv_tokens`` share plus the
    token the step writes), averaged, over the mean device time of a
    ``jit_serve_decode_step`` run. Idle time between steps is left out."""
    steps = [s for s in et.named("engine.decode")
             if s.args.get("horizon") == 1]
    runs = et.program(DECODE_STEP)
    if not steps or not runs:
        return None
    least = 0.0
    for s in steps:
        # decode_step is linear in each context, so one context holding
        # them all beside slots - 1 empty ones gives the same numbers
        ctxs = [s.args["kv_tokens"] + s.args["slots"]] \
            + [0] * (s.args["slots"] - 1)
        least += flops.roofline_seconds(*flops.decode_step(cfg, ctxs),
                                        peaks)[0]
    device_s = sum(e.dur for e in runs) / 1e9
    return 100.0 * (least / len(steps)) / (device_s / len(runs))
