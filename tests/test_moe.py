"""MoE: capacity semantics, no-drop equivalence with dense expert mixture,
router weight normalization, aux loss bounds."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.models import moe as moe_lib
from repro.models.layers import silu


def _arch(cf=8.0, top_k=2, experts=4):
    """A capacity-dispatch MoE with renormalised top-k weights (the routing
    of jamba and llama4; deepseek-moe itself is dropless with raw
    weights)."""
    base = smoke_config("deepseek-moe-16b")
    return dataclasses.replace(
        base, moe=dataclasses.replace(base.moe, capacity_factor=cf,
                                      top_k=top_k, num_experts=experts,
                                      num_shared_experts=0, norm_topk=True,
                                      dropless=False))


def _dense_mixture(arch, p, x):
    """Reference: run every expert on every token, weight by normalized top-k."""
    moe = arch.moe
    logits = x.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    top_w, top_ids = jax.lax.top_k(probs, moe.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    w = p["experts"]
    outs = []
    for e in range(moe.num_experts):
        h = silu(x @ w["w1"][e]) * (x @ w["w3"][e])
        outs.append(h @ w["w2"][e])
    outs = jnp.stack(outs, axis=-2)                      # [B,S,E,D]
    gate = jnp.zeros(probs.shape).at[
        jnp.arange(x.shape[0])[:, None, None],
        jnp.arange(x.shape[1])[None, :, None], top_ids].set(top_w)
    return jnp.einsum("bse,bsed->bsd", gate, outs)


def test_moe_matches_dense_mixture_when_no_drops():
    arch = _arch(cf=8.0)
    key = jax.random.key(0)
    p = moe_lib.init_moe(key, arch, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, arch.d_model))
    y, aux = moe_lib.apply_moe(arch, p, x)
    y_ref = _dense_mixture(arch, p, x)
    np.testing.assert_allclose(y, y_ref, atol=1e-4)
    assert 0.0 <= float(aux) < 1.0


def test_capacity_drops_reduce_output_norm():
    x = jax.random.normal(jax.random.key(1), (2, 32, 128))
    arch_hi = _arch(cf=8.0)
    arch_lo = dataclasses.replace(
        arch_hi, moe=dataclasses.replace(arch_hi.moe, capacity_factor=0.25))
    p = moe_lib.init_moe(jax.random.key(0), arch_hi, jnp.float32)
    y_hi, _ = moe_lib.apply_moe(arch_hi, p, x)
    y_lo, _ = moe_lib.apply_moe(arch_lo, p, x)
    # dropped tokens contribute zero -> strictly less mass
    assert float(jnp.sum(jnp.abs(y_lo))) < float(jnp.sum(jnp.abs(y_hi)))


def test_capacity_per_row():
    arch = _arch()
    assert moe_lib.capacity_per_row(1, arch.moe) >= 1
    c = moe_lib.capacity_per_row(4096, arch.moe)
    assert c * arch.moe.num_experts >= 4096 * arch.moe.top_k


def test_eff_capacity_reproduces_unpadded_dispatch():
    """The chunked-prefill contract: a prompt served in one PADDED chunk
    must drop exactly the tokens a full-(unpadded-)prompt dispatch drops.
    Trailing padding can never displace a real token (the stable expert
    sort keeps padded entries behind every real one), but the padded shape
    inflates ``capacity_per_row`` — ``eff_capacity`` pins the threshold to
    the real prompt's bucket, making real-token outputs bit-identical to
    the unpadded run even when capacity binds."""
    arch = _arch(cf=0.6)                       # capacity binds hard
    p = moe_lib.init_moe(jax.random.key(0), arch, jnp.float32)
    n_valid, s = 10, 16
    x_pad = jax.random.normal(jax.random.key(1), (1, s, arch.d_model))
    x_real = x_pad[:, :n_valid]
    cap_real = moe_lib.capacity_per_row(n_valid, arch.moe)
    y_pad, _ = moe_lib.apply_moe(arch, p, x_pad,
                                 eff_capacity=jnp.int32(cap_real))
    y_real, _ = moe_lib.apply_moe(arch, p, x_real)
    assert jnp.array_equal(y_pad[:, :n_valid], y_real)
    # negative control: without eff_capacity, the padded shape's larger
    # bucket keeps tokens the unpadded dispatch drops — i.e. this scenario
    # really exercises bound capacity
    u_pad, _ = moe_lib.apply_moe(arch, p, x_pad)
    assert not jnp.array_equal(u_pad[:, :n_valid], y_real)
    # eff_capacity >= the shape's own bucket is an exact no-op
    cap_shape = moe_lib.capacity_per_row(s, arch.moe)
    y_same, _ = moe_lib.apply_moe(arch, p, x_pad,
                                  eff_capacity=jnp.int32(cap_shape))
    assert jnp.array_equal(y_same, u_pad)


def _dropless_arch(held_count=0):
    """deepseek-moe's routing at a tiny size: 8 experts, top 2 with raw
    softmax weights, two shared experts, dropless."""
    base = smoke_config("deepseek-moe-16b")
    return dataclasses.replace(
        base, moe=dataclasses.replace(
            base.moe, num_experts=8, top_k=2, num_shared_experts=2,
            norm_topk=False, dropless=True, held_count=held_count))


def _uncut_reference(arch, p, x):
    """The whole dropless layer in plain jnp: every expert on every token,
    weighted by the raw top-k softmax probabilities, plus the shared
    experts."""
    moe = arch.moe
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], -1)
    top_w, top_ids = jax.lax.top_k(probs, moe.top_k)
    gate = jnp.sum(jax.nn.one_hot(top_ids, moe.num_experts) * top_w[..., None],
                   axis=-2)                                  # [B, S, E]
    w = p["experts"]
    outs = jnp.stack([(silu(x @ w["w1"][e]) * (x @ w["w3"][e])) @ w["w2"][e]
                      for e in range(moe.num_experts)], axis=-2)
    sh = p["shared"]
    shared = (silu(x @ sh["w1"]) * (x @ sh["w3"])) @ sh["w2"]
    return jnp.einsum("bse,bsed->bsd", gate, outs) + shared, top_ids


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of the layer (2 experts each, the router and the
    shared experts alike on every chip) add up, with the shared experts
    counted once, to the uncut reference layer; each share's counters count
    the assignments to its own experts. A chip holds the first experts of
    its own numbering: share ``j`` is the layer with experts ``2j, 2j+1``
    numbered first (the router's columns and the expert weights rolled
    alike), which routes every token to the same experts."""
    full = _dropless_arch()
    p = moe_lib.init_moe(jax.random.key(0), full, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, full.d_model))
    want, top_ids = _uncut_reference(full, p, x)
    y_full, counts = moe_lib.serve_moe(full, p, x)
    np.testing.assert_allclose(y_full, want, rtol=1e-5, atol=1e-5)
    assert counts.tolist() == [2 * 16 * 2,
                               len(np.unique(np.asarray(top_ids)))]
    sh = p["shared"]
    shared = (silu(x @ sh["w1"]) * (x @ sh["w3"])) @ sh["w2"]
    total = -3 * shared
    share = _dropless_arch(2)
    for first in (0, 2, 4, 6):
        ps = dict(p, router=jnp.roll(p["router"], -first, axis=1),
                  experts=jax.tree.map(
                      lambda w: jnp.roll(w, -first, axis=0)[:2],
                      p["experts"]))
        y, c = moe_lib.serve_moe(share, ps, x)
        ids = np.asarray(top_ids)
        mine = (ids >= first) & (ids < first + 2)
        assert c.tolist() == [int(mine.sum()),
                              len(np.unique(ids[mine]))]
        total = total + y
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_dropless_masked_rows_route_nowhere():
    """``row_mask``: a masked row (an empty decode slot, a chunk's padding)
    gets only the shared experts and counts no assignment; the other rows
    are untouched."""
    arch = _dropless_arch()
    p = moe_lib.init_moe(jax.random.key(0), arch, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 8, arch.d_model))
    mask = jnp.arange(8)[None] < 5
    y, c = moe_lib.serve_moe(arch, p, x, row_mask=mask)
    y_all, _ = moe_lib.serve_moe(arch, p, x)
    np.testing.assert_array_equal(y[:, :5], y_all[:, :5])
    sh = p["shared"]
    shared = (silu(x @ sh["w1"]) * (x @ sh["w3"])) @ sh["w2"]
    np.testing.assert_allclose(y[:, 5:], shared[:, 5:], rtol=1e-6, atol=1e-6)
    assert int(c[0]) == 5 * arch.moe.top_k


def test_training_forward_of_a_dropless_config_keeps_capacity_dispatch():
    """``dropless`` is a serving setting: the training forward of a dropless
    config is the capacity dispatch of the same config without it (about
    ``top_k * capacity_factor`` expert passes a token, as
    ``core.analytical`` counts), and at a capacity that drops tokens it
    differs from the dropless serving forward."""
    arch = dataclasses.replace(_dropless_arch(), moe=dataclasses.replace(
        _dropless_arch().moe, capacity_factor=0.5))
    cap_arch = dataclasses.replace(arch, moe=dataclasses.replace(
        arch.moe, dropless=False))
    p = moe_lib.init_moe(jax.random.key(0), arch, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, arch.d_model))
    y, aux = moe_lib.apply_moe(arch, p, x)
    y_cap, aux_cap = moe_lib.apply_moe(cap_arch, p, x)
    np.testing.assert_array_equal(y, y_cap)
    assert float(aux) == float(aux_cap)
    y_serve, _ = moe_lib.serve_moe(arch, p, x)
    assert not np.allclose(y, y_serve, rtol=1e-3, atol=1e-3)
