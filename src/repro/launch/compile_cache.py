"""JAX's persistent compilation cache for the launchers and the chip smoke.

A full-width step compiles in tens of seconds; with the cache on, a second
process (or a second run on the same machine) reads the executable back.
The cache key includes the directory, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (gitignored); src/repro/launch/ -> checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and is
    left alone; otherwise the cache lives at the fixed :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
