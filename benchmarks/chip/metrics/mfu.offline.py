"""The whole decode step's share of the chip's peak over the window: per
decode step the larger of its FLOPs over the bf16 peak and its bytes
(weights read once plus the live keys and values) over the HBM bandwidth,
summed over the window's steps and divided by the window. Decode is bound
by the bytes."""
from lib import flops


def read(ctx):
    steps = ctx.get("decode_steps") if ctx["kind"] == "serve" else None
    if not steps:
        return None
    least = sum(flops.roofline_seconds(*flops.decode_step(ctx["config"], c),
                                       ctx["peaks"])[0]
                for c in steps.values())
    return 100.0 * least / ctx["seconds"]
