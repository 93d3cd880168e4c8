"""JAX's persistent compilation cache at one fixed place.

Entry points (``chip_smoke.py``, the ``benchmarks`` CLIs) call
``enable_compile_cache()`` once, before their first compile; importing this
module sets nothing. A cache path is part of what makes an entry hit, so the
directory is never derived from a temp dir, a pid or a clock.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the cache dir.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing. Otherwise the cache goes to ``.jax_cache`` at the checkout
    root, which ``.gitignore`` lists.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
