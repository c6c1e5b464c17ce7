"""A MoE decode step's share of its roofline in the traced window: the least
time of each traced decode step (``moe_flops.decode_step``: its FLOPs over
the bf16 peak or its bytes over the HBM bandwidth, the weights outside the
routed experts, the held experts the step touched and every live key and
value read once; from the ``slots`` and ``kv_tokens`` of the step's
``engine.decode`` span and the expert counters of the ``engine.emit`` that
follows it), averaged, over the mean device time of a run of
``jit_serve_decode_step``. Idle time between steps is left out, as in
``decode_step_roofline.offline``, which counts a dense MLP in every layer.
A program without the counters leaves nothing to read."""
from lib import engine_trace, flops, moe_flops


def read(ctx):
    et = engine_trace.for_run(ctx)
    if et is None:
        return None
    runs = et.program(engine_trace.DECODE_STEP)
    cfg = ctx["config"]
    least, steps, decode = 0.0, 0, None
    for s in et.inside(et.spans):
        if s.name == "engine.decode" and s.args.get("horizon") == 1:
            decode = s
        elif s.name == "engine.emit" and decode is not None:
            if "expert_rows" in s.args:
                slots = decode.args["slots"]
                # linear in each context: one holding them all is the same
                ctxs = [decode.args["kv_tokens"] + slots] + [0] * (slots - 1)
                least += flops.roofline_seconds(*moe_flops.decode_step(
                    cfg, ctxs, s.args["expert_rows"],
                    s.args["experts_touched"]), ctx["peaks"])[0]
                steps += 1
            decode = None
    if not steps or not runs:
        return None
    device_s = sum(e.dur for e in runs) / 1e9
    return 100.0 * (least / steps) / (device_s / len(runs))
