"""JAX's persistent compilation cache, placed from outside or at a fixed path.

Entry points that run on an accelerator (``chip_smoke.py``,
``repro.launch.serve``) call :func:`enable_compile_cache` before their
first compile.  Importing ``repro`` never turns the cache on, so test
processes compile exactly as before.
"""
from __future__ import annotations

import os
import pathlib

# <checkout>/.jax_cache — fixed, because the cache directory is part of
# what a later process must name to find the entries again.  git-ignored.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX and nothing
    is set here; otherwise the cache goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
