"""Where the launch entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout's own .jax_cache/: a fixed path, because the path is part
# of the cache's key and a directory that moves never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself
    and nothing here sets another.  Otherwise the cache is
    :data:`CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
