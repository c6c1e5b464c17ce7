"""Plain per-expert oracle of the grouped expert MLP (``ops.moe_experts``),
and its path off the TPU.

Every held expert runs its SwiGLU over every token; a token's output is the
sum, over its picks, of the picked expert's output times the pick's weight.
Picks whose expert id lies outside ``[0, E)`` (experts held elsewhere, or
rows masked out) add nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def swiglu(x, w1, w3, w2):
    """One expert: ``(silu(x w1) * (x w3)) w2`` in the operands' dtype."""
    a = x @ w1
    return (a * jax.nn.sigmoid(a) * (x @ w3)) @ w2


def moe_experts(x, expert_ids, weights, w1, w3, w2):
    """x [T, D], expert_ids / weights [T, k] (local ids, f32 weights),
    w1 / w3 [E, D, F], w2 [E, F, D] -> y [T, D] in x's dtype, summed in
    float32."""
    def one(y, e_w):
        e, a, c, b = e_w
        gate = jnp.sum(jnp.where(expert_ids == e, weights, 0.0), axis=-1)
        out = swiglu(x, a.astype(x.dtype), c.astype(x.dtype),
                     b.astype(x.dtype))
        return y + gate[:, None] * out.astype(jnp.float32), None

    e = jnp.arange(w1.shape[0], dtype=expert_ids.dtype)
    y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                        (e, w1, w3, w2))
    return y.astype(x.dtype)
