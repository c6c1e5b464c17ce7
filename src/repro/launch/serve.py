"""Serving driver with two engines behind ``--engine {static,continuous}``.

static      the original fixed-batch driver: one dense KV cache of
            ``batch * (prompt_len + gen_len)`` rows, every request padded to
            the worst case and decoded in lock-step.
continuous  ``repro.serving.ContinuousEngine``: paged KV cache + scheduler —
            requests are admitted/recycled mid-flight, prompts are ingested
            by chunked prefill, shared prompt prefixes are served from the
            refcounted prefix cache (``--no-prefix-cache`` to disable), and
            live KV memory tracks actual generated lengths. ``--decode-steps
            N`` moves N decode iterations into one compiled on-device loop
            per host dispatch (token streams stay bit-identical to N=1).
            Serves every
            decode-state-protocol family — dense, MoE, VLM, pure-SSM
            (mamba2), hybrid (jamba) — with prefix caching auto-gated off
            for SSM-bearing archs (recurrent state is not page-decomposable;
            an explicit ``--prefix-cache`` is rejected up front).

Sampling (``--temperature/--top-k/--top-p/--seed``) is valid for BOTH
engines: request ``i`` gets ``SamplingParams(seed = --seed + i)`` and both
paths draw from the shared ``repro.serving.sampling`` sampler, whose PRNG
key is ``fold_in(key(seed), position)`` — so the two engines emit identical
token ids for the same prompts at any temperature, not just greedy
(tested in tests/test_serving.py and tests/test_sampling.py).

``python -m repro.launch.serve --arch llama3.2-3b --smoke --engine continuous``
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, smoke_config
from ..models import build_model
from ..serving.sampling import (SamplingParams, fused_sampling_enabled,
                                sample_tokens)
from ..train.steps import serve_params
from .compile_cache import enable_compile_cache


def _fused(args) -> bool:
    """--sampler beats the REPRO_FUSED_SAMPLING env default."""
    if args.sampler is not None:
        return args.sampler == "fused"
    return fused_sampling_enabled()


def _request_seed(args, i: int) -> int:
    """Request i is seeded ``--seed + i`` (mod 2^32 — the sampler's key
    width) in BOTH engines, which is what makes their streams comparable."""
    return (args.seed + i) % (2 ** 32)


def _sampling_arrays(args, batch):
    """Per-request sampler inputs for the static path."""
    return (jnp.asarray([_request_seed(args, i) for i in range(batch)],
                        jnp.uint32),
            jnp.full((batch,), args.temperature, jnp.float32),
            jnp.full((batch,), args.top_k, jnp.int32),
            jnp.full((batch,), args.top_p, jnp.float32))


def _run_static(model, params, args, arch) -> dict:
    b, plen, glen = args.batch, args.prompt_len, args.gen_len
    max_len = plen + glen
    caches = model.init_caches(None, b, max_len)
    prompt = jax.random.randint(jax.random.key(1), (b, plen), 5,
                                arch.vocab_size)
    batch = {"tokens": prompt}
    if arch.family == "encdec":
        batch["frontend_embeddings"] = jax.random.normal(
            jax.random.key(2), (b, arch.enc_seq_len, arch.d_model)
        ).astype(jnp.dtype(arch.dtype))

    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    if args.temperature > 0:
        filtered = args.top_k > 0 or args.top_p < 1.0
        fused = _fused(args) and filtered
        sample = jax.jit(sample_tokens,
                         static_argnames=("filtered", "fused"))
        seeds, temps, top_ks, top_ps = _sampling_arrays(args, b)

        def pick(logits, pos):
            # the sampler folds each request's stream position into its key,
            # matching the continuous engine draw for draw
            return sample(logits, seeds, jnp.full((b,), pos, jnp.int32),
                          temps, top_ks, top_ps, filtered=filtered,
                          fused=fused)
    else:
        # greedy stays a pure argmax — no sampler sorts/keys on the default
        # path (bit-identical by the sampler's temperature-0 contract, and
        # the same specialization the continuous engine's static flag does)
        def pick(logits, pos):
            return jnp.argmax(logits, axis=-1)

    t0 = time.perf_counter()
    logits, caches = prefill(params, caches, batch)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    # the prompt's next token sits at stream position plen; each decode step
    # i then emits position plen + 1 + i
    tokens = pick(logits[:, -1], plen)
    generated = [tokens]
    t0 = time.perf_counter()
    for i in range(glen - 1):
        db = {"tokens": tokens[:, None],
              "positions": jnp.full((b,), plen + i, jnp.int32)}
        logits, caches = decode(params, caches, db)
        tokens = pick(logits[:, -1], plen + 1 + i)
        generated.append(tokens)
    jax.block_until_ready(generated[-1])
    t_decode = time.perf_counter() - t0

    out = np.stack([np.asarray(t) for t in generated], axis=1)
    print(f"[serve/static] {arch.name}: prefill {plen} tok x{b} in "
          f"{t_prefill*1e3:.1f}ms | {glen} decode steps in "
          f"{t_decode*1e3:.1f}ms ({t_decode/max(glen-1,1)*1e3:.1f} ms/tok)")
    print(f"[serve/static] sample generations (first 8 ids/row): "
          f"{out[:2, :8].tolist()}")
    return {"tokens": out, "t_prefill": t_prefill, "t_decode": t_decode}


def _run_continuous(model, params, args, arch) -> dict:
    from ..serving import ContinuousEngine, Request, pages_needed

    b, plen, glen = args.batch, args.prompt_len, args.gen_len
    prompt = np.asarray(jax.random.randint(jax.random.key(1), (b, plen), 5,
                                           arch.vocab_size))
    max_seq = plen + glen
    num_pages = args.num_pages or (
        b * pages_needed(max_seq + 1, args.page_size) + 2)
    engine = ContinuousEngine(model, params, num_slots=args.slots or b,
                              num_pages=num_pages, page_size=args.page_size,
                              max_seq_len=max_seq + args.page_size,
                              prefix_cache=args.prefix_cache,
                              prefill_chunk=args.prefill_chunk or None,
                              tp=args.tp, fused_sampling=_fused(args),
                              decode_steps=args.decode_steps,
                              fused_decode=args.fused_decode)
    reqs = [Request(uid=i, prompt=[int(t) for t in prompt[i]],
                    max_new_tokens=glen,
                    sampling=SamplingParams(temperature=args.temperature,
                                            top_k=args.top_k,
                                            top_p=args.top_p,
                                            seed=_request_seed(args, i)))
            for i in range(b)]
    t0 = time.perf_counter()
    results = engine.run(reqs)
    wall = time.perf_counter() - t0
    errors = {i: r["error"] for i, r in results.items() if "error" in r}
    if errors:
        raise RuntimeError(f"requests failed: {errors}")
    out = np.stack([np.asarray(results[i]["tokens"]) for i in range(b)])
    total_tokens = out.size
    print(f"[serve/continuous] {arch.name}: {b} requests x {glen} tokens in "
          f"{wall*1e3:.1f}ms ({total_tokens/wall:.1f} tok/s, "
          f"{engine.steps} decode steps, {engine.prefills} prefills, "
          f"{engine.prefill_tokens} prompt tokens computed / "
          f"{engine.cached_prefill_tokens} from prefix cache)")
    print(f"[serve/continuous] sample generations (first 8 ids/row): "
          f"{out[:2, :8].tolist()}")
    stats = {"tokens": out, "wall": wall, "engine": engine,
             "steps": engine.steps,
             "prefills": engine.prefills,
             "decode_dispatches": engine.decode_dispatches,
             "decode_exits": dict(engine.decode_exits),
             "prefill_tokens": engine.prefill_tokens,
             "cached_prefill_tokens": engine.cached_prefill_tokens,
             "prefix_cache_off_reason": engine.prefix_cache_off_reason}
    if args.decode_steps > 1:
        print(f"[serve/continuous] decode-steps={args.decode_steps}: "
              f"{engine.decode_dispatches} host dispatches for "
              f"{engine.steps} decode steps "
              f"(exits: {dict(engine.decode_exits)})")
    if engine.prefix_cache_off_reason:
        print(f"[serve/continuous] {engine.prefix_cache_off_reason}")
    if engine.fused_decode_off_reason:
        print(f"[serve/continuous] {engine.fused_decode_off_reason}")
    stats["fused_decode"] = engine.fused_decode
    stats["fused_decode_off_reason"] = engine.fused_decode_off_reason
    if args.tp > 1:
        tps = engine.tp_stats()
        print(f"[serve/continuous] tp={args.tp}: "
              f"{tps['collective_bytes_per_device'] / 1e6:.2f} MB "
              f"all-reduced per device, "
              f"{tps['per_device']['kv_bytes'] / 1e6:.2f} MB KV per device "
              f"({tps['per_device']['pages_in_use']} pages, head-sharded)")
        stats["tp_stats"] = tps
    return stats


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    # sampling (both engines; request i is seeded --seed + i)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax; > 0 scales logits before the "
                         "categorical draw")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass in (0, 1] (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base PRNG seed: params init + per-request "
                         "sampling seeds (--seed + request index)")
    ap.add_argument("--sampler", choices=("fused", "ref"), default=None,
                    help="top-k/top-p filter implementation: the sort-free "
                         "streaming kernel (default) or the sort-based "
                         "reference. Token streams are bit-identical; 'ref' "
                         "is a fallback/debugging path (default from "
                         "REPRO_FUSED_SAMPLING, unset = fused)")
    # continuous-engine knobs
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree over a 1-D device mesh "
                         "(continuous engine only; must divide the query "
                         "heads and either divide or be a multiple of the "
                         "KV heads — the latter replicates KV shards; MoE "
                         "experts shard expert-parallel; on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count)")
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots (default: --batch)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV pool pages (default: sized to the request set)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="share cached prompt-prefix pages across requests "
                         "(default: on for attention-only archs; forced off "
                         "for SSM-bearing archs, whose recurrent decode "
                         "state is not page-decomposable)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked-prefill tokens per step, a page multiple "
                         "(default: 4 pages)")
    ap.add_argument("--decode-steps", type=int, default=1,
                    help="decode iterations per host dispatch: N > 1 runs a "
                         "compiled on-device loop that early-exits on "
                         "EOS/budget/page exhaustion, cutting host syncs by "
                         "~N while keeping token streams bit-identical "
                         "(continuous engine only)")
    ap.add_argument("--fused-decode", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="fused decode residual stream + streaming LM-head "
                         "epilogue (no [S, V] logits buffer; token streams "
                         "bit-identical either way). Default from "
                         "REPRO_FUSED_DECODE (unset = on); auto-falls back "
                         "with a recorded reason for post-norm stacks, MLM "
                         "heads, and non-tile-aligned TP vocab shards "
                         "(continuous engine only)")
    args = ap.parse_args(argv)
    # one validation for BOTH engines (the static path reads raw args, so
    # without this it would silently reinterpret e.g. --top-p 0)
    try:
        sp = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                            top_p=args.top_p, seed=args.seed)
    except ValueError as e:
        ap.error(str(e))
    if sp.greedy and sp.filtered:
        ap.error("--top-k/--top-p have no effect at --temperature 0 "
                 "(greedy argmax); set --temperature > 0 to sample")
    if args.tp > 1 and args.engine != "continuous":
        ap.error("--tp requires --engine continuous")
    if args.decode_steps < 1:
        ap.error("--decode-steps must be >= 1")
    if args.decode_steps > 1 and args.engine != "continuous":
        ap.error("--decode-steps requires --engine continuous (the static "
                 "driver decodes in lock-step, one token per dispatch)")
    if args.fused_decode is not None and args.engine != "continuous":
        ap.error("--fused-decode requires --engine continuous (the static "
                 "driver always materializes full logits)")

    arch = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    assert not arch.bidirectional, "encoder-only archs have no decode step"
    if args.engine == "continuous":
        from ..serving.engine import SERVABLE_FAMILIES
        if arch.family not in SERVABLE_FAMILIES:
            ap.error(f"--engine continuous serves families "
                     f"{SERVABLE_FAMILIES}; {arch.name} is {arch.family!r} "
                     "(use --engine static)")
    # an EXPLICIT --prefix-cache on an SSM-bearing arch fails here with the
    # reason, not as an assertion deep in the engine (the static engine has
    # no prefix cache; the flag only gates continuous). The default stays
    # True so the engine itself performs the SSM gate and records the
    # reason in every result — resolving it to False here would skip that
    # marker and turn the gate into the silent no-op it must never be.
    if args.prefix_cache and arch.family in ("ssm", "hybrid") \
            and args.engine == "continuous":
        ap.error(f"--prefix-cache is unsupported for {arch.family} archs "
                 f"({arch.name}): SSM recurrent decode state is not "
                 "page-decomposable, so cached KV pages cannot be shared; "
                 "rerun without --prefix-cache")
    if args.prefix_cache is None:
        args.prefix_cache = True
    enable_compile_cache()
    model = build_model(arch)
    params = serve_params(model, arch, args.seed)

    if args.engine == "continuous":
        return _run_continuous(model, params, args, arch)
    return _run_static(model, params, args, arch)


if __name__ == "__main__":
    main()
