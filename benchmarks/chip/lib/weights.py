"""Weights from the seed, made by the benchmark and not by the program.

The program's parameter tree gives only names and shapes (``jax.eval_shape``
of its init, which computes nothing); every value here comes from the seed,
so the plain reference can make the very same weights again without taking
anything the program made. One jitted call makes them all on the device, in
the dtype they are used in.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))


def _fill(path, leaf, key):
    """One leaf: norm scales near 1, biases and embedding tables small,
    matrices at fan-in scale (the last axis but one is the input)."""
    name = _leaf_name(path)
    k = jax.random.fold_in(key, zlib.crc32(jax.tree_util.keystr(path)
                                           .encode()) & 0x7FFFFFFF)
    z = jax.random.normal(k, leaf.shape, jnp.float32)
    if name == "scale":
        return 1.0 + 0.1 * z
    if name in ("embedding", "pos_embedding"):
        return 0.02 * z
    stacked = "blocks" in jax.tree_util.keystr(path)
    matrix_ndim = 3 if stacked else 2
    if len(leaf.shape) < matrix_ndim:       # a bias, stacked or not
        return 0.02 * z
    return z / jnp.sqrt(jnp.float32(leaf.shape[-2]))


def generate(structure, key, dtype):
    """A tree like ``structure`` (ShapeDtypeStructs) filled from ``key``."""
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _fill(p, l, key).astype(dtype), structure)


def make(structure, key, dtype):
    """``generate`` as one jitted call on the default device."""
    return jax.jit(lambda k: generate(structure, k, dtype))(key)
