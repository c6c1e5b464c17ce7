"""Model FLOP utilization of training over the traced window: the model's
FLOPs per token (6 N for the matmuls plus 12 L s d for attention, forward
and backward, no recomputation) times tokens per second, over chips times
the chip's bf16 peak."""
from lib import flops


def read(ctx):
    r = ctx.get("reduced")
    if ctx["kind"] != "train" or r is None or not ctx.get("traced_steps"):
        return None
    rate = ctx["traced_steps"] * ctx["tokens_per_step"] / r.window_s
    per_token = flops.train_flops_per_token(ctx["config"], ctx["seq_len"])
    return 100.0 * per_token * rate / (ctx["device"]["count"]
                                       * ctx["peaks"]["bf16_flops"])
