"""Process-level JAX settings shared by the entry scripts
(``chip_smoke.py``, ``benchmarks/run.py``)."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing
    is set here (JAX reads the variable itself). Otherwise the cache is
    ``.jax_cache/`` at the checkout root: a fixed path, since the path
    is part of what a later run must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
