"""The serving-path Pallas kernels compile for a TPU v5e chip.

Nothing here runs on a chip: each kernel is lowered and compiled at
internlm2-1.8b serving widths for one chip of a *described* ``v5e:2x2``
topology (the TPU compiler is installed, no chip is attached). Interpret-mode
parity tests cannot see what this catches — blocks off the (8, 128) tiling,
primitives Mosaic has no lowering for, verifier errors — and it costs about
two seconds a kernel. The topology is described inside a fixture, never at
import time, because only one process may hold the TPU library.
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
# the offline-decode benchmark cell: 16 slots, 2048-token contexts
CELL_SLOTS, CELL_MAX_PAGES, CELL_POOL = 16, 128, 2050
CELL_POOL_SHAPE = ((CELL_POOL, PAGE, HKV, D), BF)
CASES = {
    "paged_decode_attention": (
        attn.paged_decode_attention_fwd,
        [((SLOTS, HQ, D), BF), POOL_SHAPE, POOL_SHAPE,
         ((SLOTS, MAX_PAGES), I32), ((SLOTS,), I32)]),
    "paged_decode_attention_cell": (
        attn.paged_decode_attention_fwd,
        [((CELL_SLOTS, HQ, D), BF), CELL_POOL_SHAPE, CELL_POOL_SHAPE,
         ((CELL_SLOTS, CELL_MAX_PAGES), I32), ((CELL_SLOTS,), I32)]),
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


def test_benchmark_reads_kernel_functions_through_the_names(compiled_text):
    """The chip benchmark's ``kernel_names`` still maps the named decode
    attention and LM head instructions to the kernel functions its readers
    look up: a ``name=`` ending in ``_kernel`` would displace them."""
    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "lib" / "trace.py")
    spec = importlib.util.spec_from_file_location("chipbench_lib_trace",
                                                  path)
    trace = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = trace      # its dataclasses look it up
    spec.loader.exec_module(trace)
    for case, kernel in (("paged_decode_attention", "_paged_decode_kernel"),
                         ("paged_decode_attention_cell",
                          "_paged_decode_kernel"),
                         ("fused_lm_head_sampled", "_head_kernel")):
        names = trace.kernel_names(compiled_text(case))
        assert names, case
        for key, found in names.items():
            assert key.startswith(KERNEL_NAMES[case]), key
            assert found == kernel, (case, found)
    assert not [n for n in KERNEL_NAMES.values() if n.endswith("_kernel")]
