"""The serving-path Pallas kernels compile for a TPU v5e chip.

Nothing here runs on a chip: each kernel is lowered and compiled at
internlm2-1.8b serving widths for one chip of a *described* ``v5e:2x2``
topology (the TPU compiler is installed, no chip is attached). Interpret-mode
parity tests cannot see what this catches — blocks off the (8, 128) tiling,
primitives Mosaic has no lowering for, verifier errors — and it costs about
two seconds a kernel. The topology is described inside a fixture, never at
import time, because only one process may hold the TPU library.
"""
import os

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
CASES = {
    "paged_decode_attention": (
        attn.paged_decode_attention_fwd,
        [((SLOTS, HQ, D), BF), POOL_SHAPE, POOL_SHAPE,
         ((SLOTS, MAX_PAGES), I32), ((SLOTS,), I32)]),
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


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name} compiled without its Pallas kernel"
