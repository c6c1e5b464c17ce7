"""Arithmetic the per-layer metric readers (``metrics/*.py``) share. Each
reader gets the run's context: ``kind``, ``config``, ``device``, ``peaks``
and, in a traced run, ``reduced`` (``lib.trace.Reduced``); a training run
adds ``hlo_text``, ``traced_steps``, ``tokens_per_step`` and ``seq_len``; a
serving run adds ``records``, ``num_slots``, ``seconds`` and the decode steps of the window and of the
traced part of it (``decode_steps``, ``traced_decode_steps``: token time ->
the context length of each sequence the step advanced)."""
from __future__ import annotations

from typing import Callable, Optional

from . import flops


def idle_share(ctx: dict) -> Optional[float]:
    """100 (1 - busy / window) over the traced window."""
    r = ctx.get("reduced")
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)


def kernel_roofline(ctx: dict, kernel: str, calls_per_step: int,
                    cost: Callable[[list], tuple]) -> Optional[float]:
    """100 x (least time of the kernel's work) / (its summed device time)
    over the traced decode steps. The kernel's ops are those the decode
    step's compiled HLO names ``kernel`` (``ctx["kernels"]``: op key ->
    kernel function); ``cost(ctxs) -> (flops, bytes)`` is its work in one
    step, averaged over the steps the records saw in the traced span and
    counted for as many steps as the trace holds calls."""
    r, steps = ctx.get("reduced"), ctx.get("traced_decode_steps")
    keys = [k for k, name in ctx.get("kernels", {}).items() if name == kernel]
    if r is None or not steps or not keys:
        return None
    spent = sum(r.op_seconds.get(k, 0.0) for k in keys)
    calls = sum(r.op_count.get(k, 0) for k in keys)
    if spent <= 0 or calls == 0:
        return None
    least = sum(flops.roofline_seconds(*cost(c), ctx["peaks"])[0]
                for c in steps.values()) / len(steps)
    return 100.0 * least * (calls / calls_per_step) / spent
