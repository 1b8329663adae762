"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` at the top of ``main()``,
never at import.  ``JAX_COMPILATION_CACHE_DIR``, when set, names the
directory and nothing here overrides it.  Otherwise the cache lives at a
fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
path is part of the cache key, so every run of one checkout finds what an
earlier run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``src/repro/launch/compile_cache.py`` -> ``<repo>/.jax_cache``
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The environment's cache directory if set, else the checkout's."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory.  Call before the first compile."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
