"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``)
call :func:`place_compile_cache` before their first compile; library
modules and tests never do. A run that sets ``JAX_COMPILATION_CACHE_DIR``
keeps that directory, which JAX reads itself. Otherwise the cache lives in
``<checkout>/.jax_cache``: a fixed path, because the path is part of the
cache key and a directory that moves never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns that path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
