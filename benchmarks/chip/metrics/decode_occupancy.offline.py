"""Decode tokens emitted in the window over (decode steps x slots), from
the tokens the engine recorded: how full the decode batch ran."""


def read(ctx):
    steps = ctx.get("decode_steps") if ctx["kind"] == "serve" else None
    if not steps:
        return None
    tokens = sum(len(c) for c in steps.values())
    return 100.0 * tokens / (len(steps) * ctx["num_slots"])
