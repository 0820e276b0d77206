"""Which body a Pallas kernel of ``ops/`` is traced as."""

from __future__ import annotations

import os

import jax

__all__ = ["kernel_path"]


def kernel_path() -> str:
    """``mosaic`` | ``interpret`` | ``jnp``.  The one decision from the
    backend and the two environment variables, for the flash kernels'
    dispatch (``parallel/ring_attention.py:_flash_dispatch``, which also
    wants blocks that fit, and quotes ``MVTPU_NO_FLASH`` in its log line), the
    scan (``ops/kda.py``) and EVA attention (``ops/flash_eva.py``) alike.
    Taken at trace time, so a compiled step holds whichever body this named
    and never switches.

    - TPU backend: the compiled Mosaic kernel.
    - ``MVTPU_FORCE_FLASH`` (any non-empty value) off-TPU: the same kernel
      in interpret mode, so CI covers the kernels' own arithmetic.
    - ``MVTPU_NO_FLASH`` anywhere, or neither: the jnp reference path."""
    if os.environ.get("MVTPU_NO_FLASH"):
        return "jnp"
    if jax.default_backend() == "tpu":
        return "mosaic"
    return "interpret" if os.environ.get("MVTPU_FORCE_FLASH") else "jnp"
