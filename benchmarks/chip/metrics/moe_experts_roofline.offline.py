"""The grouped expert kernel's share of its roofline in the traced decode
steps: the least time its work needs (``moe_flops.expert_kernel``: each
touched held expert's weights read once, 2 x 3 d f FLOPs a row, from the
``expert_rows`` and ``experts_touched`` the engine records on
``engine.emit``, summed over a step's MoE layers) over its summed device
time. The kernel is the one the decode step's compiled HLO names
``_moe_experts_kernel``; it runs once per MoE layer and step. A program
without the counters or the kernel leaves nothing to read."""
from lib import engine_trace, flops, moe_flops


def read(ctx):
    et = engine_trace.for_run(ctx)
    r = ctx.get("reduced")
    keys = [k for k, name in ctx.get("kernels", {}).items()
            if name == "_moe_experts_kernel"]
    if et is None or r is None or not keys:
        return None
    emits = [s for s in et.named("engine.emit") if "expert_rows" in s.args]
    spent = sum(r.op_seconds.get(k, 0.0) for k in keys)
    calls = sum(r.op_count.get(k, 0) for k in keys)
    if not emits or spent <= 0 or calls == 0:
        return None
    cfg = ctx["config"]
    least = sum(flops.roofline_seconds(
        *moe_flops.expert_kernel(cfg, s.args["expert_rows"],
                                 s.args["experts_touched"]),
        ctx["peaks"])[0] for s in emits) / len(emits)
    return 100.0 * least * (calls / moe_flops.moe_layers(cfg)) / spent
