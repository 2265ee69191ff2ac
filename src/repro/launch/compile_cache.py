"""JAX's persistent compilation cache for the entry points.

Called from each launcher's ``main()`` (never at import, so tests and
library callers keep JAX's defaults).  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins: JAX reads it itself and nothing here overrides it.  Otherwise
the cache lives at a fixed ``.jax_cache/`` in the checkout root — the
directory is part of each entry's key, so a path that moved between runs
(a temporary name, a pid, a timestamp) would never hit.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
