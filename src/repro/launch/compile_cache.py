"""JAX's persistent compilation cache for the launchers and `chip_smoke.py`.

The cache key includes its directory, so the directory must not move
between runs: `$JAX_COMPILATION_CACHE_DIR` when it is set (JAX reads that
variable itself), otherwise one fixed directory inside the checkout,
`<checkout>/.jax_cache` (listed in `.gitignore`). Entry points call
`enable_compile_cache()` from `main()`; importing this module changes
nothing, and the test suite never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the fixed
    in-checkout one."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir()`
    and return it."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
