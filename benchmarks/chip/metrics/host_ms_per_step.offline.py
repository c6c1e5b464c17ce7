"""The host's serial time per decode step in the traced window: the median,
over the engine's ``engine.step`` spans that dispatch a decode step, of the
span less the time the host waited on the device in it
(``engine.decode_wait``, ``engine.prefill_wait``), in ms."""
from lib import engine_trace


def read(ctx):
    et = engine_trace.for_run(ctx)
    return None if et is None else engine_trace.host_ms_per_step(et)
