"""Where the entry points keep JAX's persistent compilation cache."""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The fixed in-checkout cache directory (git-ignored).  A fixed path
#: matters: it is part of the cache key, so a moving directory never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set
    this sets no other directory.  Otherwise the cache goes to
    ``DEFAULT_DIR``.  Entry points call this from ``main``, never at
    import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
