"""The one rule for JAX's persistent compilation cache.

The cache key includes the directory, so a directory that moves (a cwd-
relative name, a temporary directory, a pid or a time in the path) never
hits.  Every entry point that wants compiled programs to survive the
process calls :func:`configure` and nothing else sets the directory:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX already reads it; nothing is
  overridden, the cache is only made sure to be on;
* otherwise — ``<checkout>/.jax_cache``, an absolute path worked out from
  this package's own location (listed in ``.gitignore``).
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The directory the rule resolves to (absolute)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def configure(min_compile_secs: float = 0.5, *,
              already_compiled: bool = False) -> str:
    """Apply the rule; returns the cache directory in use.

    ``min_compile_secs`` is the smallest compile worth persisting (serving
    cold starts pass 0: they are death by a thousand small compiles).
    ``already_compiled``: the process may have compiled before this call —
    JAX latches the cache on or off at its first compile, so the latched
    state is dropped and the next compile initialises against the
    directory."""
    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    if already_compiled:
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    return path
