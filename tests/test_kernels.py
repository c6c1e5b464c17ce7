"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dep (requirements-dev.txt)
    given = settings = st = None

from repro.kernels.bias_gelu import kernel as bg_kernel, ref as bg_ref
from repro.kernels.fused_lamb import ops as lamb_ops, ref as lamb_ref
from repro.kernels.fused_layernorm import kernel as ln_kernel, ref as ln_ref
from repro.kernels.fused_softmax import kernel as sm_kernel, ref as sm_ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(256, 128), (512, 384), (1024, 1024)])
@pytest.mark.parametrize("rms", [True, False])
def test_layernorm_kernel_sweep(shape, dtype, rms):
    x = jax.random.normal(jax.random.key(0), shape, dtype)
    res = jax.random.normal(jax.random.key(1), shape, dtype)
    scale = jnp.ones((shape[-1],)) * 1.1
    bias = None if rms else jnp.full((shape[-1],), 0.05)
    yk = ln_kernel.fused_residual_layernorm(x, res, scale, bias, rms=rms,
                                            interpret=True)
    yr = ln_ref.fused_residual_layernorm(x, res, scale, bias, rms=rms)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("with_bias", [True, False])
def test_bias_gelu_kernel(dtype, with_bias):
    x = jax.random.normal(jax.random.key(2), (512, 256), dtype)
    b = jnp.linspace(-1, 1, 256).astype(dtype) if with_bias else None
    yk = bg_kernel.bias_gelu(x, b, interpret=True)
    yr = bg_ref.bias_gelu(x, b)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32), atol=atol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (256, 384)])
@pytest.mark.parametrize("kind", ["rmsnorm", "layernorm"])
def test_decode_residual_norm_kernel_bitwise(shape, dtype, kind):
    """The decode-path residual+norm fusion is a BIT-exactness contract,
    not a tolerance one: the kernel adds in the model dtype and duplicates
    ``_apply_norm`` op-for-op, so jit'd kernel (interpret) and jit'd ref
    must agree exactly — this is what lets the engine's paged stack
    promise the static engine's token streams."""
    y = jax.random.normal(jax.random.key(0), shape, dtype)
    x = jax.random.normal(jax.random.key(1), shape, dtype)
    scale = jnp.linspace(0.8, 1.2, shape[-1]).astype(jnp.float32)
    bias = None if kind == "rmsnorm" \
        else jnp.linspace(-0.1, 0.1, shape[-1]).astype(jnp.float32)
    hk, xk = jax.jit(lambda y, x: ln_kernel.decode_residual_norm(
        y, x, scale, bias, kind=kind, interpret=True))(y, x)
    hr, xr = jax.jit(lambda y, x: ln_ref.decode_residual_norm(
        y, x, scale, bias, kind=kind))(y, x)
    assert jnp.array_equal(xk, xr), "fused residual add is not bit-exact"
    assert jnp.array_equal(hk, hr), "fused norm output is not bit-exact"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(8, 128), (64, 256)])
def test_gated_rmsnorm_kernel_bitwise(shape, dtype):
    """Same bit-exactness contract for the mamba epilogue's SiLU-gated
    RMSNorm (``models.ssm`` delegates to the ref — the kernel must match
    it exactly for the ssm families' fused decode)."""
    y = jax.random.normal(jax.random.key(2), shape, dtype)
    z = jax.random.normal(jax.random.key(3), shape, dtype)
    scale = jnp.linspace(0.9, 1.1, shape[-1]).astype(jnp.float32)
    ok = jax.jit(lambda y, z: ln_kernel.gated_rmsnorm(
        y, z, scale, interpret=True))(y, z)
    orf = jax.jit(lambda y, z: ln_ref.gated_rmsnorm(y, z, scale))(y, z)
    assert jnp.array_equal(ok, orf)


# ------------------------------------------------ fused training/prefill blocks


@pytest.mark.parametrize("name", ["bert-large", "llama3.2-3b",
                                  "jamba-v0.1-52b"])
def test_fused_blocks_tolerance_parity(name):
    """REPRO_FUSED_BLOCKS routes apply_block's residual+norm (and the gelu
    MLP's bias+activation) through the fused kernels. Unlike fused decode
    this is a tolerance contract — the training fusion adds in fp32 where
    the unfused block adds in model dtype — so forward logits must agree
    to rounding, not bitwise. bert-large covers the post-norm
    ``fused_residual_layernorm`` sites (the paper's Fig-13 pattern) and
    ``bias_gelu``; llama covers the pre-norm mixer-add + ln2 fusion; jamba
    the hybrid mamba/attn periods."""
    from repro.configs import smoke_config
    from repro.models import build_model
    arch = smoke_config(name)
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    batch = {"tokens": jnp.asarray(rng.integers(5, arch.vocab_size, (2, 16)))}

    def fwd(flag, monkey=pytest.MonkeyPatch()):
        monkey.setenv("REPRO_FUSED_BLOCKS", flag)
        try:
            logits, _ = jax.jit(model.forward)(params, batch)
        finally:
            monkey.undo()
        return np.asarray(logits, np.float32)

    ref, fused = fwd("0"), fwd("1")
    np.testing.assert_allclose(fused, ref, atol=3e-2, rtol=1e-2)


def test_fused_blocks_default_off(monkeypatch):
    from repro.models.transformer import fused_blocks_enabled
    monkeypatch.delenv("REPRO_FUSED_BLOCKS", raising=False)
    assert fused_blocks_enabled() is False
    monkeypatch.setenv("REPRO_FUSED_BLOCKS", "1")
    assert fused_blocks_enabled() is True


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(4, 128, 128), (2, 256, 64)])
def test_softmax_kernel(shape, causal):
    s = jax.random.normal(jax.random.key(3), shape, jnp.float32)
    yk = sm_kernel.scale_mask_softmax(s, scale=0.125, causal=causal,
                                      interpret=True)
    yr = sm_ref.scale_mask_softmax(s, scale=0.125, causal=causal)
    np.testing.assert_allclose(yk, yr, atol=1e-6)
    rows = np.asarray(yk.sum(-1))
    np.testing.assert_allclose(rows, np.ones_like(rows), atol=1e-5)


if st is not None:
    @settings(max_examples=8, deadline=None)
    @given(rows=st.sampled_from([1, 3, 8]),
           f=st.sampled_from([64, 256, 2048]),
           seed=st.integers(0, 100))
    def test_lamb_kernel_property_sweep(rows, f, seed):
        ks = jax.random.split(jax.random.key(seed), 4)
        w = jax.random.normal(ks[0], (rows, f), jnp.float32)
        g = jax.random.normal(ks[1], (rows, f), jnp.float32)
        m = jax.random.normal(ks[2], (rows, f), jnp.float32) * 0.1
        v = jnp.abs(jax.random.normal(ks[3], (rows, f))) * 0.01
        kw = dict(ginv=0.3, c1=1.5, c2=1.2, beta1=0.9, beta2=0.999, eps=1e-6,
                  weight_decay=0.01, lr=3e-4)
        outk = lamb_ops.lamb_stage12(w, g, m, v, interpret=True, **kw)
        outr = lamb_ref.lamb_stage12(w, g, m, v, red_axes=(-1,), **kw)
        for a, b in zip(outk, outr):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)
else:
    def test_lamb_kernel_property_sweep():
        pytest.importorskip("hypothesis")
