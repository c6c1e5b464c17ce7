"""A MoE decode cell's share of the chip's peak over the traced window: per
traced decode step the larger of its FLOPs over the bf16 peak and its bytes
over the HBM bandwidth (``moe_flops.decode_step``: the weights outside the
routed experts, the held experts the step touched and every live key and
value read once; from the ``slots`` and ``kv_tokens`` of the step's
``engine.decode`` span and the expert counters of the ``engine.emit`` that
follows it), summed and divided by the traced window. A program without the
counters leaves nothing to read."""
from lib import engine_trace, flops, moe_flops


def read(ctx):
    et = engine_trace.for_run(ctx)
    r = ctx.get("reduced")
    if et is None or r is None or r.window_s <= 0:
        return None
    cfg = ctx["config"]
    least, decode = 0.0, None
    found = False
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
                found = True
            decode = None
    return 100.0 * least / r.window_s if found else None
