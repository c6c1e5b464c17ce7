"""Smoke test of the main paths on a TPU, through the normal entry points.

    python chip_smoke.py           # one chip: kernels, serve, train
    python chip_smoke.py --tp 4    # four chips: tensor-parallel serving only

One chip runs three phases:

* kernels: the paged decode/prefill attention and fused LM-head Pallas
  kernels at internlm2-1.8b widths against their ``ref.py`` oracles;
* serve: ``repro.launch.serve.main`` with the continuous engine at
  internlm2-1.8b's published widths (random weights from ``--seed``), once
  greedy at ``--decode-steps 1`` and once sampled at ``--decode-steps 4``,
  with the runtime sanitizer on;
* train: ``repro.launch.train.main`` on BERT-large, the paper's Phase-1 job
  (batch 32, sequence 128, LAMB), for four steps.

``--tp 4`` serves internlm2-1.8b in float32 at full matmul precision at tp 4
and tp 1 in one process and counts the greedy streams that differ.

Every line before the last names the device; the last line is one JSON
object ``{"ok": true, "device": {...}}``. Without a TPU, or when any phase
fails, the script exits nonzero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "internlm2-1.8b"
REQUESTS, PROMPT, GEN = 8, 256, 32


class CompileClock:
    """Seconds spent in XLA compiles (persistent-cache loads included) and
    persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def check(cond, what):
    if not cond:
        raise AssertionError(what)


def phase_kernels(log):
    """Pallas kernels vs their reference implementations (XLA) on one small
    input each, at the serving widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as attn_ops
    from repro.kernels.decode_attention import ref as attn_ref
    from repro.kernels.fused_lm_head import ops as head_ops
    from repro.kernels.fused_lm_head import ref as head_ref
    from repro.models.layers import pad_vocab

    arch = get_config(ARCH)
    hq, hkv, d = arch.num_heads, arch.num_kv_heads, arch.resolved_head_dim
    s, page, pages, max_pages = REQUESTS, 16, 64, 19
    ks = jax.random.split(jax.random.key(0), 8)
    bf = jnp.bfloat16
    k_pool = jax.random.normal(ks[0], (pages, page, hkv, d), bf)
    v_pool = jax.random.normal(ks[1], (pages, page, hkv, d), bf)
    table = jax.random.permutation(ks[2], jnp.arange(1, pages))[
        :s * 7].reshape(s, 7)
    table = jnp.pad(table, ((0, 0), (0, max_pages - 7))).astype(jnp.int32)
    lens = jnp.asarray([1, 17, 40, 64, 90, 100, 111, 112], jnp.int32)
    q = jax.random.normal(ks[3], (s, hq, d), bf)
    got = jax.jit(attn_ops.paged_decode_attention)(q, k_pool, v_pool, table,
                                                   lens)
    want = jax.jit(attn_ref.paged_decode_attention)(q, k_pool, v_pool, table,
                                                    lens)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    log(f"paged decode attention vs ref: max abs err {err:.4g}")
    check(err < 5e-2, f"paged decode attention off by {err}")

    c = 64
    qc = jax.random.normal(ks[4], (c, hq, d), bf)
    row = table[3]
    got = jax.jit(attn_ops.paged_prefill_attention)(qc, k_pool, v_pool, row,
                                                    40, 100)
    want = jax.jit(attn_ref.paged_prefill_attention)(qc, k_pool, v_pool, row,
                                                     40, 100)
    err = float(jnp.max(jnp.abs(got[:60].astype(jnp.float32)
                                - want[:60].astype(jnp.float32))))
    log(f"paged prefill attention vs ref: max abs err {err:.4g}")
    check(err < 5e-2, f"paged prefill attention off by {err}")

    v = pad_vocab(arch.vocab_size)
    x = jax.random.normal(ks[5], (s, arch.d_model), bf)
    w = (jax.random.normal(ks[6], (arch.d_model, v), jnp.float32)
         * arch.d_model ** -0.5 * 4).astype(bf)
    temps = jnp.full((s,), 0.8, jnp.float32)
    top_k = jnp.full((s,), 40, jnp.int32)
    top_p = jnp.full((s,), 0.95, jnp.float32)
    logits = jax.jit(lambda x, w: (x @ w).astype(jnp.float32))(x, w)

    def fused(rs, sampled):
        return jax.jit(lambda *a: head_ops.head_tokens(
            *a, sampled=sampled, filtered=sampled))(x, w, rs, temps, top_k,
                                                    top_p)

    def oracle(rs):
        return jax.jit(lambda *a: head_ref.head_epilogue(
            *a, sampled=True, filtered=True))(logits, rs, temps, top_k, top_p)

    tok, ok = fused(jnp.zeros((s,), jnp.float32), False)
    lg = np.asarray(logits)
    picked = lg[np.arange(s), np.asarray(tok)]
    check(bool(np.asarray(ok).all()), "fused LM head probe flagged finite "
                                      "logits")
    check(np.all(picked >= lg.max(-1) - 0.02 * np.abs(lg.max(-1))),
          "fused LM head greedy token is not a maximal logit")
    same = draws = 0
    for i in range(4):
        rs = jax.random.uniform(jax.random.key(10 + i), (s,), jnp.float32)
        a, _ = fused(rs, True)
        b, _ = oracle(rs)
        same += int((np.asarray(a) == np.asarray(b)).sum())
        draws += s
    log(f"fused LM head: greedy tokens maximal in {s}/{s} rows; sampled "
        f"(T 0.8, top-k 40, top-p 0.95) tokens equal to the reference draw "
        f"in {same}/{draws}")
    check(same >= 0.9 * draws, f"fused LM head draws differ: {same}/{draws}")


def _custom_calls(engine):
    """Count ``tpu_custom_call`` (Pallas kernels) in the engine's compiled
    greedy decode step."""
    import jax.numpy as jnp
    step = engine._decode_fn(False, False)
    z = jnp.zeros((engine.num_slots,), jnp.int32)
    table = jnp.zeros((engine.num_slots, engine.max_pages_per_seq), jnp.int32)
    text = step.lower(engine.params, engine.pools, table, z, z,
                      *engine._null_sampling).compile().as_text()
    return text.count("tpu_custom_call")


def phase_serve(log):
    from repro.launch import serve

    os.environ["REPRO_SANITIZE"] = "1"
    base = ["--arch", ARCH, "--engine", "continuous",
            "--batch", str(REQUESTS), "--slots", str(REQUESTS),
            "--prompt-len", str(PROMPT), "--gen-len", str(GEN)]
    runs = (("greedy", ["--decode-steps", "1"]),
            ("sampled", ["--temperature", "0.8", "--top-k", "40",
                         "--top-p", "0.95", "--decode-steps", "4"]))
    for name, extra in runs:
        t0 = time.perf_counter()
        out = serve.main(base + extra)
        engine = out.pop("engine")
        toks = out["tokens"]
        check(toks.shape == (REQUESTS, GEN), f"{name}: tokens {toks.shape}")
        check(bool(((toks >= 0) & (toks < engine.arch.vocab_size)).all()),
              f"{name}: token outside the vocabulary")
        check(engine.sanitize, "sanitizer is off")
        check(engine.fused_decode and engine.fused_decode_off_reason is None,
              f"{name}: fused decode off: {engine.fused_decode_off_reason}")
        line = (f"serve {name}: {REQUESTS} requests x {GEN} tokens, "
                f"{engine.steps} decode steps in {engine.decode_dispatches} "
                f"dispatches, fused_decode on, sanitizer on, wall "
                f"{time.perf_counter() - t0:.1f}s")
        if name == "greedy":
            n = _custom_calls(engine)
            check(n > 0, "no Pallas kernel in the compiled decode step")
            line += f", tpu_custom_call in decode step: {n}"
        log(line)
        del engine, out
        gc.collect()


def phase_train(log):
    import numpy as np

    from repro.launch import train

    out = train.main(["--arch", "bert-large", "--batch", "32", "--seq", "128",
                      "--steps", "4"])
    losses = [h["loss"] for h in out["history"]]
    check(len(losses) == 4 and bool(np.isfinite(losses).all()),
          f"losses {losses}")
    log(f"train bert-large b32 s128 LAMB: losses "
        f"{', '.join(f'{x:.4f}' for x in losses)}")
    del out
    gc.collect()


def phase_tp(log, tp):
    """Greedy internlm2-1.8b streams at tp and at tp 1, float32 weights and
    full matmul precision, in one process."""
    import dataclasses

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import ContinuousEngine, Request
    from repro.train.steps import serve_params

    arch = dataclasses.replace(get_config(ARCH), dtype="float32",
                               param_dtype="float32")
    model = build_model(arch)
    rng = np.random.default_rng(1)
    prompts = rng.integers(5, arch.vocab_size, (REQUESTS, PROMPT))
    kw = dict(num_slots=REQUESTS, num_pages=REQUESTS * 19 + 2, page_size=16,
              max_seq_len=PROMPT + GEN + 16, sanitize=True)

    def serve(engine):
        t0 = time.perf_counter()
        res = engine.run([Request(uid=i, prompt=[int(t) for t in prompts[i]],
                                  max_new_tokens=GEN)
                          for i in range(REQUESTS)])
        check(not any("error" in r for r in res.values()), f"tp {engine.tp}")
        streams = [res[i]["tokens"] for i in range(REQUESTS)]
        check(all(len(t) == GEN for t in streams), f"tp {engine.tp} lengths")
        log(f"serve tp={engine.tp}: {REQUESTS} greedy streams x {GEN} "
            f"tokens, wall {time.perf_counter() - t0:.1f}s, fused_decode "
            f"{'on' if engine.fused_decode else 'off'}"
            + (f" ({engine.fused_decode_off_reason})"
               if engine.fused_decode_off_reason else ""))
        return streams

    with jax.default_matmul_precision("highest"):
        params = serve_params(model, arch, 0)
        ref = serve(ContinuousEngine(model, params, **kw))
        # the tp engine holds its own sharded copy: drop the unsharded one
        # so the memory in use shows the split
        engine = ContinuousEngine(model, params, tp=tp, **kw)
        del params
        gc.collect()
        got = serve(engine)
    used = [dev.memory_stats()["bytes_in_use"] for dev in jax.devices()]
    log("bytes_in_use per device at tp=%d: %s" % (
        tp, ", ".join(f"{d.id}: {b / 2**30:.2f} GiB"
                      for d, b in zip(jax.devices(), used))))
    diverged = sum(a != b for a, b in zip(got, ref))
    log(f"tp={tp} vs tp=1 (float32, highest precision): {diverged} of "
        f"{REQUESTS} greedy streams diverged")
    check(diverged == 0, f"{diverged} streams diverged")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=0,
                    help="serve at this tensor-parallel degree and at tp 1, "
                         "and run no other phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    clock = CompileClock()
    label = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"

    def log(msg):
        print(f"{label} {msg}", flush=True)

    log(f"device kind {dev.device_kind}, {len(devices)} device(s), compile "
        f"cache {cache or os.environ.get('JAX_COMPILATION_CACHE_DIR')}")
    phases = ([("tp", lambda log: phase_tp(log, args.tp))] if args.tp else
              [("kernels", phase_kernels), ("serve", phase_serve),
               ("train", phase_train)])
    for name, fn in phases:
        t0, c0, h0 = time.perf_counter(), clock.seconds, clock.hits
        fn(log)
        log(f"phase {name} ok: wall {time.perf_counter() - t0:.1f}s, compile "
            f"{clock.seconds - c0:.1f}s, persistent-cache hits "
            f"{clock.hits - h0}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
