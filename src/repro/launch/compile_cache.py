"""The persistent XLA compilation cache of the entry points.

Every ``main()`` that compiles for the device calls
:func:`enable_compile_cache` once, never at import.  The directory is
chosen here and nowhere else:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
  itself — a machine that provides a shared cache keeps it;
* otherwise ``.jax_cache/`` at the checkout root, a fixed path (the path
  is part of the cache key, so a directory that moves never hits).

Kernel-sized programs compile in well under JAX's default one-second
threshold, so the threshold drops to zero and they are cached too.
"""

from __future__ import annotations

import os
import pathlib

#: the checkout root: src/repro/launch/ -> three levels up
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
