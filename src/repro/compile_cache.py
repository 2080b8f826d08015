"""JAX's persistent compilation cache, placed from outside the program.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls :func:`enable` before
its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this module sets no directory.  Otherwise the cache
lives at one fixed path inside the checkout, ``<repo>/.jax_cache/``
(git-ignored): the path is part of what a later run must find again,
so it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
