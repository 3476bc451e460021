"""JAX's persistent compilation cache, as every entry point configures it.

Call :func:`enable` from an entry point's ``main()``, never at import.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this module
sets no directory of its own. Otherwise an accelerator's programs are
cached at the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``):
the path is part of the cache key, so a directory that moved would never
hit. The CPU backend gets no directory from here: its cached executables
are checked against the host's instruction set when they are read back,
and a host that differs warns or fails.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> None:
    """Cache every compiled program, however quick its compile, so that a
    second run of the same command compiles nothing."""
    if (not os.environ.get("JAX_COMPILATION_CACHE_DIR")
            and jax.default_backend() != "cpu"):
        jax.config.update("jax_compilation_cache_dir",
                          str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
