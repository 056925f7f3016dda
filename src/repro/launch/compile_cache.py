"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`configure_compile_cache` before they compile
anything.  ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and
nothing else is chosen in code.  Otherwise the cache lives at a fixed
directory inside the checkout (``<repo root>/.jax_cache``, gitignored): the
path is part of the cache key, so it is never built from a temp name, a pid
or the time, and a later run from the same checkout finds what an earlier
one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    # JAX reads the variable only when it is imported; setting the same
    # value here also covers a variable set after that.
    jax.config.update("jax_compilation_cache_dir", path)
    return path
