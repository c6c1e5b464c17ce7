"""Per-request stochastic decoding for the serving engines.

``SamplingParams`` rides on every ``Request``; ``sample_tokens`` is the one
on-device sampler both engines share (the continuous engine's decode step and
final prefill chunk, and the static driver in ``repro.launch.serve``), so a
fixed per-request seed yields the identical token stream no matter which
engine served it.

Determinism contract
--------------------
The PRNG key for the token emitted at stream position ``p`` (0-indexed over
prompt + generated tokens) of a request with seed ``s`` is::

    fold_in(key(s), p)

It depends on nothing else — not the decode slot the request landed in, not
which neighbours share the batch, not whether the token came from a decode
step or the final chunk of a (re-)prefill. That last property is what makes
recompute-preemption *forced replay*: a preempted sequence re-prefills
prompt + generated-so-far as forced context (no token is ever re-decided),
and the next token it samples uses the same ``(seed, position)`` key the
uninterrupted run would have used, so resumed sequences are token-identical
under any sampling setting.

The same property makes the multi-step compiled decode loop
(engine ``decode_steps > 1``) token-invisible: the loop derives ``p`` from
the sequence lengths it carries *in-loop* (``lens + 1``, advanced each
iteration on device), so iteration i of a dispatch draws with exactly the
key the i-th single-step dispatch would have — streams are bit-identical
at any horizon, including across a preemption landing between dispatches.

Filtering order follows the common serving convention: temperature scaling,
then top-k, then top-p (nucleus) on the rescaled distribution, then one
draw. ``temperature == 0`` short-circuits to raw ``argmax`` on the unscaled
logits — bit-identical to the historical greedy path.

The top-k/top-p masking itself lives in ``repro.kernels.fused_sampling``,
which streams it sort-free (Pallas on TPU, a bit-key bisection in jnp
elsewhere). Its sort-based ``ref.filter_logits_ref`` is the test oracle:
the two share one decision predicate, so they mask bit-identically.

The draw itself is the canonical inverse-CDF walk of
``repro.kernels.fused_lm_head.ref``: one ``jax.random.uniform`` from the
``fold_in(key(seed), position)`` key, then the first vocab index whose
(canonically tiled) prefix softmax mass exceeds ``uniform * Z``. Exact
categorical sampling, and — unlike the Gumbel-noise formulation — needing
no per-vocab-entry randomness, so the LM-head epilogue can reproduce the
identical token while streaming the unembed GEMM over vocab blocks.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels.fused_lm_head import ref as head_ref
from repro.kernels.fused_sampling import ops as fused_ops


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """How one request's tokens are chosen.

    temperature  0 = greedy argmax (the default; exact static/continuous
                 parity). > 0 divides the logits before the softmax draw.
    top_k        keep only the k highest logits (0 = disabled).
    top_p        keep the smallest set of tokens whose probability mass
                 reaches top_p (nucleus sampling; 1.0 = disabled).
    seed         per-request PRNG seed; the draw for stream position p uses
                 fold_in(key(seed), p), nothing else.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0: {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 disables): {self.top_k}")
        if not 0 < self.top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1]: {self.top_p}")
        if not 0 <= self.seed < 2 ** 32:
            raise ValueError(f"seed must fit in uint32: {self.seed}")

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    @property
    def filtered(self) -> bool:
        """True when top-k or top-p actually constrains the distribution —
        the engines skip the sampler's filtering epilogue entirely
        otherwise."""
        return self.top_k > 0 or self.top_p < 1.0


def sample_tokens(logits: jax.Array, seeds: jax.Array, positions: jax.Array,
                  temperatures: jax.Array, top_k: jax.Array,
                  top_p: jax.Array, *, filtered: bool = True) -> jax.Array:
    """Draw one token per row of ``logits`` [B, V] -> int32 [B].

    All parameter arrays are per-row [B]: ``seeds`` uint32, ``positions``
    int32 (the stream position of the token being emitted), ``temperatures``
    / ``top_p`` float32, ``top_k`` int32 (0 = disabled). Rows with
    ``temperature == 0`` return ``argmax(logits)`` on the raw logits —
    bit-identical to the greedy path — and their PRNG work is discarded.

    ``filtered`` is a static (Python) flag: pass False when every row has
    top_k and top_p disabled to skip the filtering epilogue entirely — for
    finite logits the disabled filters are exact no-ops, so both variants
    draw the identical token for the same (seed, position, logits).
    Traceable/jittable either way; nothing bigger than the [B] token
    vector ever crosses to the host.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    temps = temperatures.astype(jnp.float32)
    safe_t = jnp.where(temps > 0, temps, 1.0)
    lg = logits.astype(jnp.float32) / safe_t[:, None]

    if filtered:
        lg = fused_ops.filter_logits(lg, top_k.astype(jnp.int32),
                                     top_p.astype(jnp.float32))

    rs = head_ref.row_uniforms(seeds, positions)
    sampled = head_ref.draw_tokens(lg, rs)
    return jnp.where(temps > 0, sampled, greedy)
