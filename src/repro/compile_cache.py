"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``)
calls :func:`use_compile_cache` before it compiles anything.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
module sets nothing.  Otherwise the cache lives at one fixed path inside
the checkout, ``<repo>/.jax_cache`` (git-ignored): the path is part of
the cache key, so it must not depend on a temp dir, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
