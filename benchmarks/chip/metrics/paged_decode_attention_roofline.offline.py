"""The paged decode attention kernel's share of its roofline in the traced
decode steps: the least time its work needs (every live key and value read
once, 4 h dh ctx FLOPs per sequence and layer) over its summed device time.
The kernel is the one the decode step's compiled HLO names
``_paged_decode_kernel``; it runs once per layer and step."""
from lib import flops
from lib.readers import kernel_roofline


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    cfg = ctx["config"]
    return kernel_roofline(ctx, "_paged_decode_kernel",
                           cfg["num_hidden_layers"],
                           lambda ctxs: flops.paged_attention(cfg, ctxs))
