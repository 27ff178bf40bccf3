"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points that compile at full size (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once before their
first compile.  Library code never calls it, so importing ``repro`` leaves
JAX's cache settings alone (the test suite's topology compiles stay out of
any cache).
"""

from __future__ import annotations

import os

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed directory of the checkout (the path is part of what a later run
#: must find again, so it is never built from a temporary name).
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it too, and no
    other directory is configured); otherwise :data:`DEFAULT_DIR`."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
