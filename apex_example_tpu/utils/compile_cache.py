"""Where JAX's persistent compilation cache lives: one rule, one place.

Every entry point (train.main, serve.run_serve, bench.main, accuracy.main,
chip_smoke's children) calls :func:`enable_compile_cache` before its first
compile.  The directory is part of the cache key's lookup, so it must not
move between runs:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself; this
  code sets no directory, so whoever placed the cache from outside wins.
- unset: ``<checkout>/.jax_cache``, derived from this package's own
  location — the same path for every process started from the checkout.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
