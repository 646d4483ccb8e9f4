"""Where XLA's persistent compilation cache lives.

Every entry point that compiles (``chip_smoke.py``, ``benchmark/run.py``, the
serving and gang worker mains, the examples) calls
:func:`enable_compile_cache` before its first compile, so a second run — or a
child process — finds the programs the first one paid for. The directory is part of the cache key, so it is either
the one the environment names or ONE fixed path inside the checkout; never a
temp dir, a pid or a timestamp.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache — listed in .gitignore
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def compile_cache_dir() -> str:
    """The directory the cache uses: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else :data:`DEFAULT_CACHE_DIR`. Imports nothing — offline tools read it."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set this does nothing: JAX read the
    variable when it was imported, and no code sets another directory. Unset,
    the cache goes to :data:`DEFAULT_CACHE_DIR` through ``jax.config`` (the
    variable is only read at import, so writing ``os.environ`` alone would do
    nothing here) and is exported so child processes inherit the same path.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = DEFAULT_CACHE_DIR
    return os.environ["JAX_COMPILATION_CACHE_DIR"]
