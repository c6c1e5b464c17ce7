"""The control's lower precision: matrix products whose operands are first
rounded to float8 (e4m3) with one scale per tensor, the step below the
bfloat16 the configurations state."""
from __future__ import annotations

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn


def quantize(x, dtype=FP8):
    """x rounded to ``dtype`` under one scale that maps its largest
    magnitude to the format's largest finite value; returned in float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def mm_fp8(a, b):
    return jnp.matmul(quantize(a), quantize(b),
                      precision=jax.lax.Precision.HIGHEST)
