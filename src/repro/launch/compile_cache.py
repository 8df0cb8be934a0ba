"""Where JAX keeps its persistent compilation cache — one rule for
``chip_smoke.py`` and the benchmarks.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (listed in ``.gitignore``): a later run of the same
checkout finds what an earlier one compiled only if the directory does not
move, so the path never comes from a temporary name, a PID or the time.
"""
from __future__ import annotations

import os

import jax

#: the checkout root (this file is ``<repo>/src/repro/launch/compile_cache.py``)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
