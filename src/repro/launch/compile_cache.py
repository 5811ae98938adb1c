"""Where JAX keeps its persistent compilation cache.

Called from the entry points (``chip_smoke.py``, ``launch.serve.main``,
``launch.train.main``), never at import.  A set ``JAX_COMPILATION_CACHE_DIR``
wins and is left alone (JAX reads it itself); otherwise the cache lives at a
fixed ``.jax_cache/`` in the checkout root.  The path is part of the cache's
key, so it is never built from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
