"""The median device time of one run of the engine's decode step program
(``jit_serve_decode_step``, or ``jit_serve_decode_loop`` at more than one
step a dispatch) in the traced window, from the chip's ``XLA Modules``
line, in ms."""
from lib import engine_trace


def read(ctx):
    et = engine_trace.for_run(ctx)
    return None if et is None else engine_trace.decode_step_ms(et)
