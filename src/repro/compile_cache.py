"""Where JAX's persistent compilation cache lives.

Entry points that compile the served programs (``chip_smoke.py``,
``examples/serve_continuous.py``) call :func:`enable_compile_cache` once,
before their first compile, so later processes on the same machine reuse
the compiled decode and prefill programs instead of compiling from cold.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout and git-ignored: a path derived from a
# temporary name, a PID or the time would differ in the next process, which
# would then never find what this one cached
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left to JAX and no other directory is set here.  Otherwise the cache
    goes to ``<checkout>/.jax_cache``."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
