"""Process set-up for the programs that drive the solver.

Called explicitly by ``chip_smoke.py``, the examples and the benchmark
drivers — never as a side effect of importing ``repro`` (a library must
not pick a cache directory for the program that imports it).
"""

from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: a fixed path, because the directory is part of what
# JAX's persistent cache is keyed on — a path that moved would never hit.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache goes to
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
