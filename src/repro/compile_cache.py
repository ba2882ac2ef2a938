"""JAX's persistent compilation cache, set once before the first compile.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there by
itself and nothing here overrides it.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path (never a temporary name, a pid or
the time), so a later process of the same checkout finds what an earlier
one compiled.  The directory is listed in ``.gitignore``.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory.  Call before the first
    compile: JAX fixes the cache when it first uses it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
