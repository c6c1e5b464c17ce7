"""Share of the device's busy time in the traced training window spent in
operations whose compiled HLO holds a ``dot`` or ``convolution`` (the
paper's GEMM / non-GEMM split). Trace op names are the train step's HLO
instruction names; its compiled HLO text says which hold a GEMM."""
from lib import trace


def read(ctx):
    r = ctx.get("reduced")
    if ctx["kind"] != "train" or r is None or not ctx.get("hlo_text"):
        return None
    total = sum(r.op_seconds.values())
    if total <= 0:
        return None
    gemm = trace.gemm_ops(ctx["hlo_text"])
    return 100.0 * sum(s for key, s in r.op_seconds.items()
                       if key.split(" = ", 1)[0] in gemm) / total
