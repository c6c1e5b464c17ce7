"""Host spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation``: while a
``jax.profiler`` trace runs it records ``name`` on the host thread's line of
the trace, on the same clock as the device planes, with each keyword
argument as an event stat; with the profiler off it costs a couple of
microseconds. Every span of the program goes through here. Its names carry
the prefix of the layer that records them (``engine.`` for the serving
engine), never ``bench.``, which the chip benchmark keeps for its own spans
around its calls into the program.

Arguments are ints the caller already holds: a span never syncs the device
or builds an array to fill one.
"""
from __future__ import annotations

import jax


def span(name: str, **args: int) -> jax.profiler.TraceAnnotation:
    """A context manager recording ``name`` (and ``args``) as one span."""
    return jax.profiler.TraceAnnotation(name, **args)
