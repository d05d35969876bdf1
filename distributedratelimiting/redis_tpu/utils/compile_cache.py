"""JAX's persistent compile cache, placed from outside or at a fixed path.

Every entry point that compiles for the chip (``chip_smoke.py``,
``perfbench/harness.py``, the store server, ``examples/testapp.py``) calls
:func:`enable_compile_cache` once, before its first compile:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself; nothing here
  sets another path.
- unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``). The path
  is part of the cache key, so it is fixed: no tmp name, pid or time.
- ``JAX_ENABLE_COMPILATION_CACHE=false`` (the test suite's conftest):
  nothing is turned on.
"""

from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the cache on; returns its directory, or ``None`` when off."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    # Cache every program: the admission kernels compile in well under
    # jax's default one-second floor, and a cold chip run compiles dozens.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
