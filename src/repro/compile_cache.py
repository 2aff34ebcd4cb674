"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` before their first compile; importing
``repro`` never does. A set ``JAX_COMPILATION_CACHE_DIR`` is read by JAX
itself, so nothing is set then. Otherwise the cache goes to one fixed
directory in the checkout: the directory is part of what a later run
must find again, so it is never built from a temp name, a pid or the
time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
