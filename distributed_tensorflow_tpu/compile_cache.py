"""Where JAX's persistent compilation cache lives — one rule, every entry point.

``train.py``, ``serve.py``, ``chip_smoke.py``, ``benchmark/run.py`` and the
scripts all call ``configure()`` before their first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set in
  code, so whoever launches the program places the cache.
- unset: one fixed directory inside the checkout (``.jax_cache/``, ignored
  by git).  The path is part of the cache key, so it never carries a
  temporary name, a pid or a time — two processes started from the same
  checkout share their compiles.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure() -> str:
    """Place the compile cache; returns the directory in use."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
