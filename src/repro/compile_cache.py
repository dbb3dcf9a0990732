"""Where JAX keeps this program's persistent compilation cache.

A cold run on the chip compiles every program it runs, which can take a
large share of a short run; the persistent cache lets a later process that
runs the same programs load them instead. The cache directory is part of
what a lookup matches on, so it must not move between runs.
"""
from __future__ import annotations

import os

#: The in-tree default (listed in ``.gitignore``): a fixed path inside the
#: checkout, never a temporary name.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def configure() -> str:
    """Place JAX's persistent compilation cache; call before the first compile.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    changes nothing. Otherwise the cache goes to :data:`DEFAULT_DIR`.
    Returns the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
