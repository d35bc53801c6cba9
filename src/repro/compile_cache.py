"""JAX's persistent compilation cache, placed for the entry points.

The engine compiles one program per (plan, kind, layout, measure, pow2
batch, pow2 capacity) bucket, so a cold process spends much of its
first minutes compiling.  Entry points (``chip_smoke.py``, the
benchmarks, the examples) call ``enable_compile_cache()`` first thing;
the library never does so on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# what a later run has to find again (listed in .gitignore).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache for this process and
    return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    is left alone; otherwise the cache lives at ``DEFAULT_DIR``.
    Programs are cached however quickly they compiled: most of the
    engine's take 1-3 s, under JAX's default 1 s floor or just over."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
