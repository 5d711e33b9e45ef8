"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
:func:`use_compile_cache` first thing in ``main()``; importing the package
never touches the cache. JAX reads ``JAX_COMPILATION_CACHE_DIR`` on its own,
so when that is set nothing is set here. Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout: a fixed path, because the path
is part of what a later run looks up, and a directory that moved would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
