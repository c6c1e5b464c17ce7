"""Production meshes.

Single pod  : (data=16, model=16)              — 256 chips (one v5e pod).
Multi pod   : (pod=2, data=16, model=16)       — 512 chips; the pod axis carries
              hierarchical data parallelism over DCN.
Defined as functions so importing this module never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make(shape, axes):
    # Auto axes: shardings propagate pjit-style from the constraints
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes):
    return _make(shape, axes)


def make_tp_mesh(tp: int):
    """1-D ("model",) mesh over ``tp`` devices — the serving engine's
    tensor-parallel mesh (head-sharded paged KV + Megatron projections).
    CPU CI gets its devices from XLA_FLAGS=--xla_force_host_platform_device_count."""
    n = len(jax.devices())
    assert n >= tp, (
        f"tp={tp} needs {tp} devices, found {n} "
        "(set XLA_FLAGS=--xla_force_host_platform_device_count=N on CPU)")
    return make_mesh((tp,), ("model",))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over host (CPU) devices for tests."""
    n = data * model
    assert len(jax.devices()) >= n, (len(jax.devices()), n)
    return make_mesh((data, model), ("data", "model"))
