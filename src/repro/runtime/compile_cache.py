"""JAX's persistent compilation cache, set up in one place.

A cold run on the chip recompiles every step program (seconds to a minute
each); the persistent cache lets the next process with the same programs
load them instead.  Its directory is part of the cache's identity, so it is
never derived from a temp dir, a pid or a time: either the deployment says
where it lives (``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself) or it
is the fixed ``.jax_cache/`` directory at the root of this checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/runtime/compile_cache.py → the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already uses it and nothing
    else is set; otherwise the cache goes to ``<checkout>/.jax_cache``.
    Calling it again returns the same directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
