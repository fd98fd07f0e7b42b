"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a path that moves between runs
never hits.  :func:`enable` therefore picks one fixed place: the directory
in ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, so nothing is set in code), and ``<checkout>/.jax_cache``
otherwise.  Entry points call it once, before their first compile; the
tests never do, so they run with the cache off.
"""

from __future__ import annotations

import os
import pathlib

#: The fixed fallback: ``.jax_cache`` at the root of the checkout.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
