"""JAX's persistent compilation cache for the entry points.

Entry points (``launch/serve.py``'s ``main``, ``chip_smoke.py``) call
``enable_compile_cache`` once, before their first compile; importing the
library turns nothing on, so tests and library users keep JAX's defaults.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Keep compiled programs for the next process, and return where.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here. Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout: a fixed path, since the directory is part of
    what a later run must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
