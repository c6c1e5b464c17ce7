"""Per-arch smoke tests: reduced config, one forward/train step, shapes + no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED, REGISTRY, get_config, smoke_config
from repro.models import build_model
from repro.models.layers import pad_vocab


def _batch(arch, b=2, s=32):
    tokens = jax.random.randint(jax.random.key(2), (b, s), 5, arch.vocab_size)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, 1),
             "loss_mask": jnp.ones((b, s), jnp.bfloat16)}
    if arch.family == "encdec":
        batch["frontend_embeddings"] = jnp.ones(
            (b, arch.enc_seq_len, arch.d_model), jnp.bfloat16)
    if arch.frontend == "vision_stub":
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(s)[None, None], (3, b, s)).astype(jnp.int32)
    return batch


# the scan/remat train-step compiles take tens of seconds for the deep or
# multi-component archs; keep a fast representative subset in tier-1
_HEAVY = {"jamba-v0.1-52b", "bert-large", "llama4-maverick-400b-a17b",
          "whisper-base"}


@pytest.mark.parametrize(
    "name", [pytest.param(n, marks=pytest.mark.slow) if n in _HEAVY else n
             for n in sorted(REGISTRY)])
def test_forward_and_train_step(name):
    arch = smoke_config(name)
    model = build_model(arch)
    params = model.init(jax.random.key(0))
    batch = _batch(arch)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (2, 32, pad_vocab(arch.vocab_size))
    assert bool(jnp.isfinite(logits).all()), name
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    gnorm = sum(float(jnp.sum(jnp.square(g.astype(jnp.float32))))
                for g in jax.tree.leaves(grads))
    assert gnorm > 0 and jnp.isfinite(gnorm)


@pytest.mark.parametrize("name", sorted(ASSIGNED))
def test_full_config_matches_assignment(name):
    full = get_config(name)
    # spot-check the assignment table is encoded exactly
    expected = {
        "mistral-large-123b": (88, 12288, 96, 8, 28672, 32768),
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "internlm2-1.8b": (24, 2048, 16, 8, 8192, 92544),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "deepseek-moe-16b": (28, 2048, 16, 16, 10944, 102400),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202048),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "qwen2-vl-2b": (28, 1536, 12, 2, 8960, 151936),
    }[name]
    got = (full.num_layers, full.d_model, full.num_heads, full.num_kv_heads,
           full.d_ff, full.vocab_size)
    assert got == expected, (name, got, expected)


def test_param_counts_match_nameplates():
    tol = {"mistral-large-123b": (110e9, 130e9),
           "command-r-35b": (28e9, 38e9),
           "llama4-maverick-400b-a17b": (380e9, 420e9),
           "jamba-v0.1-52b": (48e9, 56e9),
           "deepseek-moe-16b": (15e9, 18e9),
           "mamba2-1.3b": (1.2e9, 1.5e9),
           "bert-large": (0.3e9, 0.36e9)}
    for name, (lo, hi) in tol.items():
        p = get_config(name).param_count()
        assert lo <= p <= hi, (name, p)


def test_moe_active_params():
    c = get_config("deepseek-moe-16b")
    assert c.param_count(active_only=True) < 0.25 * c.param_count()
