"""Where XLA's persistent compilation cache lives.

One rule for every entry point that compiles (``DeepSpeedTPUEngine`` and
``FastGenEngine`` call :func:`ensure_compile_cache` first thing):

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads that variable itself, so
  the program sets nothing; whoever runs it places (and moves) the cache
  from outside.
* unset — ``<checkout>/.jax_cache``, derived from this package's own
  path. The directory is part of the cache key, so it is never a
  temporary name, a pid or a timestamp: two processes of one checkout
  must land on the same entries.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_cache_dir() -> str:
    return os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point JAX at the persistent cache; returns the directory in use."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    path = default_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
