"""The decode step's share of its roofline in the traced window: the least
time of each traced decode step (its FLOPs over the bf16 peak or its bytes,
the weights read once and every live key and value, over the HBM
bandwidth; from the ``slots`` and ``kv_tokens`` of the engine's
``engine.decode`` span) over the device time of a run of
``jit_serve_decode_step``. Idle time between steps is left out."""
from lib import engine_trace


def read(ctx):
    et = engine_trace.for_run(ctx)
    return None if et is None else engine_trace.decode_step_roofline(
        et, ctx["config"], ctx["peaks"])
