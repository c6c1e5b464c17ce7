"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

On a TPU the trace holds one plane per chip, ``/device:TPU:<n>``, whose
line ``XLA Ops`` has one event per executed HLO instruction, named by the
instruction's HLO text (``%convolution_add_fusion.12 = bf16[...] fusion(...)``)
on the same clock as the host's ``python`` line, where the benchmark's own
spans (``bench.*``) sit. Control-flow instructions (``while``,
``conditional``, ``call``) enclose the instructions of their bodies, which
are listed too.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench."
_CONTROL = re.compile(r"\s(while|conditional|call)\(")


@dataclasses.dataclass
class Event:
    name: str
    start: int      # ns
    dur: int        # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    # device plane name -> line name -> events
    devices: Dict[str, Dict[str, List[Event]]]
    spans: List[Event]          # the benchmark's host spans


def find_xplane(directory: Path) -> Path:
    found = sorted(glob.glob(f"{directory}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return Path(found[-1])


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[str, Dict[str, List[Event]]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {
                line.name: [Event(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
                for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, int(e.start_ns),
                                           int(e.duration_ns)))
    spans.sort(key=lambda e: e.start)
    return Trace(devices=devices, spans=spans)


_HEAD = re.compile(r"^(.*?)\s([a-z][\w\-]*)\(")


def op_key(text: str) -> str:
    """``name = <output shape>`` of an instruction, from its trace event
    name or its line of HLO text: names repeat across programs, names with
    their output shapes do not."""
    name, _, rest = re.sub(r"^\s*(ROOT\s+)?%?", "", text).partition(" = ")
    m = _HEAD.match(rest)
    return f"{name} = {m.group(1) if m else rest[:200]}"


def is_control(event_name: str) -> bool:
    return bool(_CONTROL.search(event_name.split(" = ", 1)[-1][:4000]))


def union(intervals: Iterable[Tuple[int, int]], lo: int, hi: int
          ) -> List[Tuple[int, int]]:
    """Merged intervals clipped to [lo, hi]."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def window(trace: Trace) -> Tuple[int, int]:
    """The traced window: from the first benchmark span's start to the last
    one's end."""
    if not trace.spans:
        raise ValueError("the trace holds no benchmark span")
    return (trace.spans[0].start, max(s.end for s in trace.spans))


def ops(trace: Trace, plane: str) -> List[Event]:
    return trace.devices[plane].get("XLA Ops", [])


def leaf_ops(trace: Trace, plane: str, lo: int, hi: int) -> List[Event]:
    """Executed instructions inside [lo, hi], control flow left out (its
    bodies' instructions are there themselves)."""
    return [e for e in ops(trace, plane)
            if e.start >= lo and e.end <= hi and not is_control(e.name)]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the chips' planes
    op_seconds: Dict[str, float]        # leaf op key -> device seconds
    op_text: Dict[str, str]             # leaf op key -> its trace name
    op_count: Dict[str, int]            # leaf op key -> executions
    idle_gaps: List[Tuple[str, float]]  # host span -> idle seconds in it

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:n]]}


def reduce(trace: Trace, planes: Optional[Sequence[str]] = None,
           between: str = "host:between-spans") -> Reduced:
    """Busy and idle time and time per leaf op over the
    traced window, averaged over ``planes`` (every chip by default). An idle
    gap goes to the innermost benchmark span around it, or to ``between``
    where none is."""
    lo, hi = window(trace)
    planes = list(planes or sorted(trace.devices))
    if not planes:
        raise ValueError("the trace holds no TPU plane")
    busy_total = 0.0
    op_seconds: Dict[str, float] = defaultdict(float)
    op_text: Dict[str, str] = {}
    op_count: Dict[str, int] = defaultdict(int)
    gap_seconds: Dict[str, float] = defaultdict(float)
    for plane in planes:
        busy = union(((e.start, e.end) for e in ops(trace, plane)), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for e in leaf_ops(trace, plane, lo, hi):
            key = op_key(e.name)
            op_seconds[key] += e.dur / 1e9 / len(planes)
            op_text.setdefault(key, e.name)
            op_count[key] += 1
        prev = lo
        for s, e in busy + [(hi, hi)]:
            if s > prev:
                gap_seconds[_host_doing(trace.spans, prev, s, between)] += \
                    (s - prev) / 1e9 / len(planes)
            prev = max(prev, e)
    gaps = sorted(gap_seconds.items(), key=lambda kv: -kv[1])
    return Reduced(window_s=(hi - lo) / 1e9,
                   busy_s=busy_total / 1e9 / len(planes),
                   op_seconds=dict(op_seconds), op_text=op_text,
                   op_count=dict(op_count),
                   idle_gaps=gaps)


def _host_doing(spans: List[Event], s: int, e: int, between: str) -> str:
    """The innermost benchmark span that covers the middle of [s, e]."""
    mid = (s + e) // 2
    inner = [sp for sp in spans if sp.start <= mid < sp.end]
    if not inner:
        return between
    return min(inner, key=lambda sp: sp.dur).name


# --------------------------------------------------------------- HLO text --

_COMP_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")


def hlo_instructions(hlo_text: str) -> Dict[str, Tuple[str, List[str]]]:
    """instruction name -> (its HLO text, computations it calls)."""
    out: Dict[str, Tuple[str, List[str]]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        body = m.group(2)
        called = re.findall(
            r"(?:calls|to_apply|body|condition)=%([\w.\-]+)", body)
        for group in re.findall(r"branch_computations=\{([^}]*)\}", body):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        out[m.group(1)] = (body, called)
    return out


def hlo_computations(hlo_text: str) -> Dict[str, List[str]]:
    """computation name -> the instruction names it holds."""
    comps: Dict[str, List[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        if line.strip() == "}":
            current = None
            continue
        head = _COMP_HEAD.match(line)
        if head and not _INSTR.match(line):
            current = head.group(1)
            comps[current] = []
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            comps[current].append(m.group(1))
    return comps


def gemm_ops(hlo_text: str) -> set:
    """Names of instructions that are, or call a computation holding, a
    ``dot`` or ``convolution``: the paper's GEMMs."""
    instrs = hlo_instructions(hlo_text)
    comps = hlo_computations(hlo_text)
    memo: Dict[str, bool] = {}

    def comp_has(comp: str, depth: int = 0) -> bool:
        if comp in memo:
            return memo[comp]
        memo[comp] = False
        found = any(instr_has(i, depth + 1) for i in comps.get(comp, []))
        memo[comp] = found
        return found

    def instr_has(name: str, depth: int = 0) -> bool:
        text, called = instrs.get(name, ("", []))
        if re.search(r"\s(dot|convolution)\(", " " + text.split(
                ", metadata=")[0]):
            return True
        return depth < 50 and any(comp_has(c, depth) for c in called)

    return {name for name in instrs if instr_has(name)}


def kernel_names(hlo_text: str) -> Dict[str, str]:
    """``op_key`` -> the Pallas kernel function's name, for every
    ``tpu_custom_call`` of a compiled program. The kernel carries no name
    of its own in the trace (its instruction is ``closed_call.N`` or
    ``_unknown_.N``); the Mosaic module it embeds keeps the Python function
    it was written as (``_paged_decode_kernel``)."""
    import base64
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        body = re.search(r'"body":"([A-Za-z0-9+/=]+)"', line)
        if not body:
            continue
        raw = base64.b64decode(body.group(1))
        names = sorted(set(re.findall(rb"[A-Za-z_][A-Za-z0-9_]*_kernel\b",
                                      raw)))
        if names:
            out[op_key(line)] = names[0].decode()
    return out
