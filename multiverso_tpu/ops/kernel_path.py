"""Which body a Pallas kernel of ``ops/`` is traced as, and what its wrapper's
own arithmetic is named."""

from __future__ import annotations

import os

import jax

__all__ = ["kernel_path", "elem"]


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


def elem():
    """The scope ``attn.elem``: what in an attention sub-layer is neither a
    weight product nor a kernel, before and after it.  In
    ``models/attention/`` the norms, rotary, reshapes and transposes,
    padding, slices, convolutions, the decay and the gates' activations; in
    the kernels' wrappers here what stands around a call (the scale folded
    into q, a backward's ``delta``, the statistics' slices and broadcasts,
    ``[B, H, T, D]`` to ``[B * H, T, D]`` and back), so that nothing under
    ``attn`` is left without a part's name or a kernel's.  A call itself
    stays outside it: its name stack is the caller's.  Name-stack metadata
    alone (``benchmarks/trace/parts.py`` reads it)."""
    return jax.named_scope("attn.elem")
