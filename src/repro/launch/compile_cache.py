"""JAX's persistent compilation cache, placed from outside the program.

Drivers call :func:`enable_compile_cache` at the top of ``main()``; it is
never turned on at import time or in tests.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself, and nothing here overrides it).
Otherwise the cache lives in one fixed directory of the checkout,
``.jax_cache/`` — never a temporary name — so a later run from the same
checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: Cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache for this process; returns its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # The Pallas kernels compile in about a second, under JAX's default
    # one-second floor; cache every program.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
