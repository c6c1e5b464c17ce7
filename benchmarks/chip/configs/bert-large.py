"""Plain float32 reference for ``bert-large.json``: MLM loss, gradients and
LAMB steps, from the published equations (Devlin et al. 2018; You et al.
2019, Algorithm 2), with the departures the configuration file lists.

It reads weights laid out as the program stores them (fused ``wqkv`` with
columns ``[q | k | v]``, layers stacked on a leading axis) but imports
nothing of the program. ``mm`` is every matrix product: float32 at the
highest precision by default; the control passes a lower-precision one.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def encoder_layer(cfg, mm, x, lp):
    """One post-LN encoder layer; x [B, S, D]."""
    b, s, d = x.shape
    h = cfg["num_attention_heads"]
    dh = d // h
    eps = cfg["layer_norm_eps"]
    a = lp["attn"]
    qkv = mm(x, a["wqkv"]) + a["bqkv"]
    q, k, v = (t.reshape(b, s, h, dh) for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
        / math.sqrt(dh)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    x = layer_norm(x + mm(o.reshape(b, s, d), a["wo"]) + a["bo"], lp["ln1"],
                   eps)
    m = lp["mlp"]
    y = mm(gelu_tanh(mm(x, m["w1"]) + m["b1"]), m["w2"]) + m["b2"]
    return layer_norm(x + y, lp["ln2"], eps)


def mlm_loss(cfg, mm, params, batch):
    """Masked-LM cross-entropy over the masked positions; the tied head
    spans every stored vocabulary row."""
    tokens = batch["tokens"]
    eps = cfg["layer_norm_eps"]
    x = params["embed"]["embedding"][tokens] \
        + params["pos"]["pos_embedding"][:tokens.shape[1]]

    @jax.checkpoint
    def body(x, lp):
        return encoder_layer(cfg, mm, x, lp), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["layer_0"])
    x = layer_norm(x, params["final_norm"], eps)
    t = mm(x, params["mlm"]["dense"]) + params["mlm"]["bias"]
    t = layer_norm(gelu_tanh(t), params["mlm"]["ln"], eps)
    logits = mm(t, params["embed"]["embedding"].T)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, batch["targets"][..., None],
                                 -1)[..., 0]
    mask = batch["loss_mask"]
    return jnp.sum((lse - picked) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _rows(path, leaf):
    """LAMB's trust ratio is per layer for stacked layer weights, per
    tensor otherwise."""
    stacked = "blocks" in jax.tree_util.keystr(path) and leaf.ndim >= 2
    return tuple(range(1, leaf.ndim)) if stacked else tuple(range(leaf.ndim))


def lamb_step(hp, t, params, grads, m, v):
    """Global-norm clipping, then one LAMB step (all in float32)."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    grads = jax.tree.map(lambda g: g * scale, grads)
    cnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["eps"]
    lr, wd = hp["learning_rate"], hp["weight_decay"]

    def one(path, w, g, m, v):
        gn = g / jnp.maximum(cnorm, 1e-12)
        m = b1 * m + (1 - b1) * gn
        v = b2 * v + (1 - b2) * jnp.square(gn)
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * w
        axes = _rows(path, w)
        wn = jnp.sqrt(jnp.sum(jnp.square(w), axes, keepdims=True))
        un = jnp.sqrt(jnp.sum(jnp.square(u), axes, keepdims=True))
        r = jnp.where((wn > 0) & (un > 0), wn / jnp.maximum(un, 1e-30), 1.0)
        return w - lr * r * u, m, v

    out = jax.tree_util.tree_map_with_path(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


@jax.jit
def unit(tree):
    """``tree`` over its global norm (zero stays zero)."""
    tree = jax.tree.map(lambda x: x.astype(jnp.float32), tree)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))
    return jax.tree.map(lambda x: x / jnp.maximum(norm, 1e-30), tree)


def train_readings(cfg, hp, params, batches, mm=mm_f32, half_batch=False,
                   direction=None, keep_direction=False):
    """Three steps from ``params`` (float32) on ``batches``. Returns the
    loss of each step, each leaf's share of the first gradient's global
    norm and its norm, and each leaf's change after the three steps.
    With ``direction`` (a gradient at any scale), ``dir_dist`` is each
    leaf's distance between the first gradient and it, both over their
    global norms; ``keep_direction`` returns the first gradient over its
    global norm as ``direction``. ``half_batch`` plants a fault: the loss
    takes the mean over the first half of the rows only."""

    @jax.jit
    def step(t, params, m, v, batch):
        if half_batch:
            batch = {k: x[:x.shape[0] // 2] for k, x in batch.items()}
        loss, grads = jax.value_and_grad(
            functools.partial(mlm_loss, cfg, mm))(params, batch)
        new, m, v = lamb_step(hp, t, params, grads, m, v)
        return loss, grads, new, m, v

    w0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses = []
    out = {}
    for t, batch in enumerate(batches, start=1):
        loss, grads, params, m, v = step(jnp.float32(t), params, m, v, batch)
        losses.append(float(loss))
        if t == 1:
            gnorms = jax.device_get(leaf_norms(grads))
            first = unit(grads)
            del grads
            if direction is not None:
                out["dir_dist"] = jax.tree.map(float, jax.device_get(
                    leaf_norms(jax.tree.map(lambda a, b: a - b, first,
                                            unit(direction)))))
            if keep_direction:
                out["direction"] = first
            del first
    total = math.sqrt(sum(float(n) ** 2 for n in jax.tree.leaves(gnorms)))
    change = jax.device_get(leaf_norms(jax.tree.map(
        lambda a, b: a - b, params, w0)))
    return dict(out, losses=losses, grad_norm=gnorms,
                grad_share=jax.tree.map(lambda n: float(n) / total, gnorms),
                change=jax.tree.map(float, change))
