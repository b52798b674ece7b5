"""Persistent XLA compilation cache, switched on by the entry points.

Library modules never touch it: only ``main()`` of a command-line entry
point calls :func:`enable_compile_cache`, before its first compile.
"""

from __future__ import annotations

import os

import jax

#: fixed cache directory inside the checkout (listed in ``.gitignore``).
#: The path is part of each entry's key, so it never depends on a temp
#: name, a pid or a time.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
