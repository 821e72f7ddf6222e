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

Either way a program's names (scopes, kernel names, source locations) are
part of its key, so that a trace never shows the names of another build.
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
    import jax

    # names are part of the key. By default JAX strips debug information
    # before it hashes a program, so an executable cached by an older
    # build, whose scopes and kernel names were others, would be loaded
    # for the same arithmetic: and a device trace carries the names of the
    # executable that ran (seen on the chip: PERF.md, PR 23)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    path = default_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
