"""The serving-path Pallas kernels compile for a TPU v5e chip.

Nothing here runs on a chip: each kernel is lowered and compiled at
internlm2-1.8b (and, for the expert kernel and MHA decode attention,
deepseek-moe-16b) serving widths for one chip of a *described* ``v5e:2x2``
topology (the TPU compiler is installed, no chip is attached). Interpret-mode
parity tests cannot see what this catches — blocks off the (8, 128) tiling,
primitives Mosaic has no lowering for, verifier errors — and it costs about
two seconds a kernel. The engine's decode step is compiled whole too, at
the benchmark cell's sizes, to check that it moves no page pool. The
topology is described inside a fixture, never at import time, because
only one process may hold the TPU library.
"""
import importlib.util
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import kernel as attn
from repro.kernels.fused_layernorm import kernel as norm
from repro.kernels.fused_lm_head import kernel as head
from repro.kernels.fused_sampling import kernel as filt
from repro.kernels.moe_experts import ops as experts

# internlm2-1.8b serving widths: 16 query / 8 KV heads of 128, d_model 2048,
# vocab 92544, 8 decode slots, 16-token pages, 64-token prefill chunks
HQ, HKV, D, DM, V = 16, 8, 128, 2048, 92544
SLOTS, PAGE, CHUNK, MAX_PAGES, POOL = 8, 16, 64, 19, 154
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """An executable for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _head(sampled, filtered):
    return (lambda x, w, rs, t, k, p: head.head_tokens(
        x, w, rs, t, k, p, sampled=sampled, filtered=filtered),
        [((SLOTS, DM), BF), ((DM, V), BF), ((SLOTS,), F32), ((SLOTS,), F32),
         ((SLOTS,), I32), ((SLOTS,), F32)])


POOL_SHAPE = ((POOL, PAGE, HKV, D), BF)
# decode attention reads a layer scan's stacked pools at a layer index
STACK_SHAPE = ((3, POOL, PAGE, HKV, D), BF)
LAYER = ((), I32)
# the offline-decode benchmark cell: 16 slots, 2048-token contexts, 24
# layers
CELL_SLOTS, CELL_MAX_PAGES, CELL_POOL = 16, 128, 2050
CELL_STACK_SHAPE = ((24, CELL_POOL, PAGE, HKV, D), BF)
# deepseek-moe-16b's share in its cell: MHA (16 KV heads, one query head
# each) in 11 scanned layers, 16 held experts of 2048 x 1408, top 6
MHA_STACK_SHAPE = ((11, CELL_POOL, PAGE, HQ, D), BF)
EXPERTS, EXPERT_FF, TOP_K = 16, 1408, 6


def _experts(tokens):
    return (experts.grouped,
            [((tokens, DM), BF), ((tokens, TOP_K), I32),
             ((tokens, TOP_K), F32), ((EXPERTS, DM, EXPERT_FF), BF),
             ((EXPERTS, DM, EXPERT_FF), BF), ((EXPERTS, EXPERT_FF, DM), BF)])


CASES = {
    "paged_decode_attention": (
        attn.paged_decode_attention_fwd,
        [((SLOTS, HQ, D), BF), STACK_SHAPE, STACK_SHAPE,
         ((SLOTS, MAX_PAGES), I32), ((SLOTS,), I32), LAYER]),
    "paged_decode_attention_cell": (
        attn.paged_decode_attention_fwd,
        [((CELL_SLOTS, HQ, D), BF), CELL_STACK_SHAPE, CELL_STACK_SHAPE,
         ((CELL_SLOTS, CELL_MAX_PAGES), I32), ((CELL_SLOTS,), I32), LAYER]),
    "paged_decode_attention_mha_cell": (
        attn.paged_decode_attention_fwd,
        [((CELL_SLOTS, HQ, D), BF), MHA_STACK_SHAPE, MHA_STACK_SHAPE,
         ((CELL_SLOTS, CELL_MAX_PAGES), I32), ((CELL_SLOTS,), I32), LAYER]),
    "moe_experts_decode": _experts(CELL_SLOTS),
    "moe_experts_prefill": _experts(CHUNK),
    "paged_prefill_attention": (
        attn.paged_prefill_attention_fwd,
        [((CHUNK, HQ, D), BF), POOL_SHAPE, POOL_SHAPE, ((MAX_PAGES,), I32),
         ((), I32), ((), I32)]),
    "fused_lm_head_greedy": _head(False, False),
    "fused_lm_head_sampled": _head(True, False),
    "fused_lm_head_filtered": _head(True, True),
    "fused_sampling_filter": (
        filt.filter_logits,
        [((SLOTS, V), F32), ((SLOTS,), I32), ((SLOTS,), F32)]),
    "gated_rmsnorm": (
        norm.gated_rmsnorm,
        [((8, 4096), BF), ((8, 4096), BF), ((4096,), F32)]),
    "decode_residual_norm_r8": (
        norm.decode_residual_norm,
        [((8, DM), BF), ((8, DM), BF), ((DM,), BF)]),
    "decode_residual_norm_r64": (
        norm.decode_residual_norm,
        [((64, DM), BF), ((64, DM), BF), ((DM,), BF)]),
}


# the ``name=`` each case's pallas_call passes: its instruction's name in
# the compiled HLO and on a profiler trace's ``XLA Ops`` line
KERNEL_NAMES = {
    "paged_decode_attention": "paged_decode_attention",
    "paged_decode_attention_cell": "paged_decode_attention",
    "paged_decode_attention_mha_cell": "paged_decode_attention",
    "moe_experts_decode": "moe_experts",
    "moe_experts_prefill": "moe_experts",
    "paged_prefill_attention": "paged_prefill_attention",
    "fused_lm_head_greedy": "fused_lm_head",
    "fused_lm_head_sampled": "fused_lm_head",
    "fused_lm_head_filtered": "fused_lm_head",
    "fused_sampling_filter": "fused_sampling_filter",
    "gated_rmsnorm": "gated_rms_norm",
    "decode_residual_norm_r8": "decode_residual_norm",
    "decode_residual_norm_r64": "decode_residual_norm",
}
_CUSTOM_CALL = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .*"
                          r'custom_call_target="tpu_custom_call"', re.M)


@pytest.fixture(scope="module")
def compiled_text(one_chip, no_persistent_cache):
    """case -> the compiled HLO text of its kernel, compiled once."""
    texts = {}

    def get(name):
        if name not in texts:
            fn, shapes = CASES[name]
            args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                    for s, d in shapes]
            texts[name] = jax.jit(fn).lower(*args).compile().as_text()
        return texts[name]
    return get


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, compiled_text):
    assert "tpu_custom_call" in compiled_text(name), \
        f"{name} compiled without its Pallas kernel"


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_instruction_is_named(name, compiled_text):
    """The kernel's ``tpu_custom_call`` instruction takes its ``name=``
    (``%paged_decode_attention.1``), not the enclosing scope's."""
    want = KERNEL_NAMES[name]
    found = _CUSTOM_CALL.findall(compiled_text(name))
    assert found and all(re.fullmatch(rf"{want}(\.\d+)?", f)
                         for f in found), found


def _bench_trace():
    """The chip benchmark's ``lib/trace.py``."""
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "lib" / "trace.py")
    spec = importlib.util.spec_from_file_location("chipbench_lib_trace",
                                                  path)
    trace = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = trace      # its dataclasses look it up
    spec.loader.exec_module(trace)
    return trace


def test_benchmark_reads_kernel_functions_through_the_names(compiled_text):
    """The chip benchmark's ``kernel_names`` still maps the named decode
    attention, LM head and expert instructions to the kernel functions its
    readers look up: a ``name=`` ending in ``_kernel`` would displace
    them."""
    trace = _bench_trace()
    for case, kernel in (("paged_decode_attention", "_paged_decode_kernel"),
                         ("paged_decode_attention_cell",
                          "_paged_decode_kernel"),
                         ("paged_decode_attention_mha_cell",
                          "_paged_decode_kernel"),
                         ("moe_experts_decode", "_moe_experts_kernel"),
                         ("fused_lm_head_sampled", "_head_kernel")):
        names = trace.kernel_names(compiled_text(case))
        assert names, case
        for key, found in names.items():
            assert key.startswith(KERNEL_NAMES[case]), key
            assert found == kernel, (case, found)
    assert not [n for n in KERNEL_NAMES.values() if n.endswith("_kernel")]


def _decode_step_text(config, one_chip, monkeypatch, *, horizon=1,
                     num_pages=8, donate=False):
    """The compiled HLO of the continuous engine's decode step (sampled
    with filters, as the benchmark compiles it) for ``config`` cut to three
    layers, at the offline-decode cell's slots and context and with every
    kernel on; ``horizon`` > 1 compiles the multi-step loop. The pools are
    shapes of ``num_pages`` pages, so no pool is made here. ``donate``
    donates them, as an engine on a chip does."""
    import dataclasses

    from repro.configs import get_config
    from repro.kernels.decode_attention import ops as attn_ops
    from repro.kernels.fused_layernorm import ops as norm_ops
    from repro.kernels.fused_lm_head import ops as head_ops
    from repro.kernels.fused_sampling import ops as filt_ops
    from repro.models import build_model
    from repro.models import transformer as tf
    from repro.serving import ContinuousEngine
    for mod in (attn_ops, norm_ops, head_ops, filt_ops, experts):
        monkeypatch.setattr(mod, "supported", lambda: True)
    arch = dataclasses.replace(get_config(config), num_layers=3)
    model = build_model(arch)
    params = jax.eval_shape(model.init, jax.random.key(0))
    engine = ContinuousEngine(model, params, num_slots=CELL_SLOTS,
                              num_pages=8, page_size=PAGE,
                              max_seq_len=CELL_MAX_PAGES * PAGE,
                              sanitize=False, decode_steps=horizon)
    engine._donate_pools = donate
    pools = jax.eval_shape(lambda: tf.init_serving_state(
        arch, num_pages, PAGE, CELL_SLOTS, jnp.dtype(arch.dtype)))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    z = jax.ShapeDtypeStruct((CELL_SLOTS,), I32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((CELL_SLOTS, CELL_MAX_PAGES), I32,
                                 sharding=one_chip)
    if horizon == 1:
        fn, per_slot = engine._decode_fn(True, True), 2
    else:
        fn, per_slot = engine._decode_multi_fn(True, True), 6
    text = fn.lower(on_chip(params), on_chip(pools), table,
                    *(z,) * per_slot,
                    *on_chip(engine._null_sampling)).compile().as_text()
    return text, pools


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*?) ([\w\-]+)\(", re.M)


@pytest.mark.parametrize("horizon", [1, 4], ids=["step", "loop"])
@pytest.mark.parametrize("config", ["internlm2-1.8b",
                                    "deepseek-moe-16b-ep4"])
def test_decode_step_moves_no_pool(config, horizon, one_chip,
                                   no_persistent_cache, monkeypatch):
    """At the cell's 2050 pages with the pools donated, the compiled decode
    step (one step, and the four-step loop) copies, slices and writes back
    no page pool: no ``copy``, ``copy-start``, ``dynamic-slice`` or
    ``dynamic-update-slice`` instruction, fused or not, has a whole layer
    stack of pools or one layer's pool as its shape. The step's rows are
    scattered into the pools in place, and decode attention reads them
    where they lie."""
    text, pools = _decode_step_text(config, one_chip, monkeypatch,
                                    horizon=horizon, num_pages=CELL_POOL,
                                    donate=True)
    shapes = set()
    for leaf in jax.tree.leaves(pools):
        assert leaf.dtype == BF and leaf.ndim in (4, 5), leaf
        # a pool [P, page, Hkv, D], or a stack [L, P, page, Hkv, D]
        for dims in {leaf.shape, leaf.shape[-4:]}:
            shapes.add(f"bf16[{','.join(map(str, dims))}]")
    assert len(shapes) >= 2, shapes
    moves = {"copy", "copy-start", "dynamic-slice", "dynamic-update-slice"}
    found = [(name, op, out) for name, out, op in _INSTR.findall(text)
             if op in moves and any(sh in out.replace(" ", "")
                                    for sh in shapes)]
    assert not found, found
    assert "paged_decode_attention" in text


def test_moe_decode_step_names_its_kernels(one_chip, no_persistent_cache,
                                           monkeypatch):
    """The continuous engine's decode step for deepseek-moe-16b's share
    (sampled with filters, as the benchmark compiles it; one dense and two
    MoE layers at the cell's widths), compiled for a v5e with every kernel
    on: ``kernel_names`` maps its expert instruction to
    ``_moe_experts_kernel`` and its attention instruction to
    ``_paged_decode_kernel``, each kernel's own source names and no other
    kernel's (traces JAX caches can carry those across kernels)."""
    text, _ = _decode_step_text("deepseek-moe-16b-ep4", one_chip,
                                monkeypatch)
    names = _bench_trace().kernel_names(text)
    found = {}
    for key, kernel in names.items():
        found.setdefault(key.split(".", 1)[0], set()).add(kernel)
    assert found["moe_experts"] == {"_moe_experts_kernel"}, names
    assert found["paged_decode_attention"] == {"_paged_decode_kernel"}, names
