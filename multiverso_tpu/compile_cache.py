"""Where JAX keeps compiled programs between processes.

A cold ~1B-parameter train step takes minutes to compile, and every new
process would pay it again.  JAX's persistent compilation cache keys each
entry on the program AND on the cache path, so the directory must not
move between runs: it is ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (jax reads that variable itself — nothing to do
here), else ``<checkout>/.jax_cache`` beside the package.  ``init()``
calls :func:`configure`; no other code sets a cache directory.
"""

from __future__ import annotations

import os

import jax

__all__ = ["configure", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure() -> str:
    """Place the compile cache; returns the directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
