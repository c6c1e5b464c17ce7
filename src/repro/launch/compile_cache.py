"""Persistent XLA compilation cache for the entry points.

``serve.main``, ``train.main`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile, so a second run of
the same program on the same device loads its executables instead of
compiling them again. Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

# the cache key includes the directory, so it must not move between runs:
# a fixed path inside the checkout (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[Path]:
    """Point JAX's persistent compilation cache at :data:`CACHE_DIR`, unless
    ``JAX_COMPILATION_CACHE_DIR`` is set — JAX then uses that directory
    itself and nothing is set here. Returns the directory it set, if any."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return CACHE_DIR
