"""JAX's persistent compilation cache, at one fixed place per checkout.

Call :func:`init_compile_cache` before anything compiles. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
sets nothing. Otherwise the cache goes to ``<checkout>/.jax_cache``
(git-ignored): the directory is part of every cache key, so it must not
move between runs — never a temp name, a pid or a timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's own cache directory (src/repro/launch -> checkout root)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
