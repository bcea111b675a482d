"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` before their first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set here.
Otherwise the cache goes to ``<checkout>/.jax_cache`` (gitignored): a fixed
path, never built from a temp name, a pid or the time, so that the next run
from the same checkout finds what this one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
