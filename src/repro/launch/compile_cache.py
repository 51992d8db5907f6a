"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` once at start-up; importing this module does
nothing.  The cache key includes the directory, so the path is fixed: no
temporary name, pid or time goes into it.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself) and no other directory is set.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
