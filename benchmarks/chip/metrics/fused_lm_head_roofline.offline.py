"""The fused LM head kernel's share of its roofline in the traced decode
steps: the least time its work needs (the head weight read once, 2 S d V
FLOPs over all S slots it computes) over its summed device time. The kernel
is the one the decode step's compiled HLO names ``_head_kernel``; it runs
once per step."""
from lib import flops
from lib.readers import kernel_roofline


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    cfg, slots = ctx["config"], ctx["num_slots"]
    return kernel_roofline(ctx, "_head_kernel", 1,
                           lambda ctxs: flops.lm_head(cfg, slots))
