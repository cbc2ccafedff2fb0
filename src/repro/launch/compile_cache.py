"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.serve``) call
:func:`use_compile_cache` once at start-up, before anything compiles; tests
and imports never do.  The cache directory is part of each entry's key, so
it must not move between runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when
that is set (JAX reads the variable itself) and otherwise the fixed
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its one fixed place and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
