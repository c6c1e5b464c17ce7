"""The one generator of serving traffic, driven by a traffic file's
parameters. Every seed gets the same prompt lengths, output lengths and
arrival gaps, in the same order (drawn from the file's ``lengths_seed``),
with token ids, system prompts and sampling seeds drawn from the run's
seed: the seed changes what is said, never how much work there is. (A
window serves only the head of a backlog, so a seed that reordered the
lengths would change the work.)

Parameters read (see ``traffic/*.json``):

* ``requests``: how many requests the trace holds;
* ``arrival``: ``backlog`` (all due at t = 0) or ``poisson`` at ``rate``
  requests per second;
* ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal, clipped;
* ``shared_prefixes``: ``{"count", "tokens"}``: each prompt starts with one
  of ``count`` system prompts of ``tokens`` tokens (0 for none);
  ``prompt`` is then the user turn after it;
* ``sampling``: ``{"temperature", "top_k", "top_p"}``;
* ``in_flight``: the first ``in_flight`` requests stand for those a server
  in steady state holds in its slots: a uniform share of each one's output
  is already generated and is part of its prompt, and only the rest is left
  to generate (0 for none).

Every ``GREEDY_EVERY``-th request decodes greedily instead of sampling: the
check compares served tokens with the reference's best, which holds for
greedy tokens only.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np

GREEDY_EVERY = 8


@dataclasses.dataclass
class TrafficRequest:
    uid: int
    prompt: List[int]
    max_new_tokens: int
    arrival: float
    greedy: bool
    seed: int            # the request's sampling seed (32 bits)


def lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def generate(traffic: dict, seed: int, vocab: int,
             n: Optional[int] = None) -> List[TrafficRequest]:
    n = traffic["requests"] if n is None else n
    sizes = np.random.default_rng(traffic["lengths_seed"])
    prompt_lens = lognormal_lengths(sizes, traffic["prompt"], n)
    output_lens = lognormal_lengths(sizes, traffic["output"], n)
    if traffic["arrival"] == "backlog":
        gaps = np.zeros(n)
    elif traffic["arrival"] == "poisson":
        gaps = sizes.exponential(1.0 / traffic["rate"], n)
    else:
        raise ValueError(f"unknown arrival {traffic['arrival']!r}")

    arrivals = np.cumsum(gaps) if gaps.any() else gaps
    shared = traffic.get("shared_prefixes") or {"count": 0, "tokens": 0}
    which = sizes.integers(max(shared["count"], 1), size=n)
    head = min(traffic.get("in_flight", 0), n)
    done = np.zeros(n, np.int64)
    done[:head] = np.minimum(
        np.floor(sizes.uniform(0.0, 1.0, head) * output_lens[:head]),
        output_lens[:head] - 1)
    rng = np.random.default_rng(np.random.SeedSequence((seed, 0x7A11)))
    systems = rng.integers(5, vocab, (shared["count"], shared["tokens"]))
    out = []
    for i in range(n):
        prompt = rng.integers(5, vocab,
                              int(prompt_lens[i] + done[i])).tolist()
        if shared["count"]:
            prompt = systems[which[i]].tolist() + prompt
        out.append(TrafficRequest(
            uid=i, prompt=prompt,
            max_new_tokens=int(output_lens[i] - done[i]),
            arrival=float(arrivals[i]), greedy=i % GREEDY_EVERY == 0,
            seed=int(rng.integers(0, 2 ** 32))))
    return out
