"""JAX's persistent compilation cache for the launchers.

A new ``TrainerApp`` or ``ServeApp`` built on a restart re-jits its step;
with the persistent cache on, that recompile is a disk read. JAX keys the
cache by the directory too, so the directory must stay put between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache, listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    other directory is set here; otherwise the cache goes to the fixed
    in-checkout ``DEFAULT_DIR``.
    """
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
