"""Device prefetch — keep H2D transfers behind compute.

The reference's ``AsyncBuffer`` (SURVEY.md §2.24) hides parameter-pull
latency behind the training step; on TPU the analogous host-side
bottleneck is the input pipeline: a ``device_put`` issued only when the
step needs its batch serializes transfer and compute.  ``jax``'s
transfers are asynchronous — ``device_put`` returns immediately with
the copy in flight — so keeping a small window of batches pre-issued
overlaps every transfer with the previous step's compute, no thread
needed (the standard flax-style prefetch pattern, re-homed here next to
its host-thread sibling :class:`~multiverso_tpu.util.AsyncBuffer`).
"""

from __future__ import annotations

import collections
from typing import Any, Iterable, Iterator, Optional

from .. import tracing

__all__ = ["prefetch_to_device"]


def prefetch_to_device(iterator: Iterable[Any], size: int = 2,
                       sharding: Optional[Any] = None) -> Iterator[Any]:
    """Yield elements of ``iterator`` with their arrays already on device.

    Each element (a pytree of host arrays) is ``jax.device_put`` up to
    ``size`` elements ahead of the consumer; with ``sharding`` (e.g. a
    ``NamedSharding`` over the data mesh axis) batches land pre-sharded,
    so the train step never reshards its input.  Non-array leaves
    (step counters, ids, strings) ride along untouched, and a leaf the
    sharding cannot apply to — a scalar array, or a final partial batch
    whose leading dim doesn't divide the axis — is replicated instead
    of raising mid-epoch (the same fallback as
    ``parallel.sharding.batch_placer``, which serves the fused apps;
    this serves arbitrary host iterators).

    ``sharding`` may also be a *callable* ``array -> placed array`` —
    e.g. the closure ``batch_placer`` returns — applied to every array
    leaf, for placement policies richer than one sharding (dtype casts,
    per-leaf divisibility fallback).

    ``size=2`` is the sweet spot for steady-state training (one batch
    computing, one in flight); larger only helps jittery producers.
    """
    if size < 1:  # validate HERE, not at first next() inside the loop
        raise ValueError(f"prefetch size must be >= 1, got {size}")
    return _prefetch_gen(iter(iterator), size, sharding)


def _prefetch_gen(it: Iterator[Any], size: int,
                  sharding: Optional[Any]) -> Iterator[Any]:
    import jax
    import numpy as np

    replicated = None
    if sharding is not None and hasattr(sharding, "mesh"):
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(sharding.mesh, PartitionSpec())

    def put_leaf(x):
        if not isinstance(x, (np.ndarray, jax.Array)):
            return x
        if callable(sharding):
            return sharding(x)
        if sharding is None:
            return jax.device_put(x)
        try:
            return jax.device_put(x, sharding)
        except ValueError:
            # Spec rank > leaf rank, or non-divisible dims: replicated
            # is correct, just unsharded.
            return jax.device_put(x, replicated) if replicated is not None \
                else jax.device_put(x)

    def put(batch):
        return jax.tree_util.tree_map(put_leaf, batch)

    queue: collections.deque = collections.deque()
    dry = object()

    def enqueue(n: int) -> None:
        # The host iterator is spanned from outside its next(), one span
        # a pull (the one pull that finds it dry included), never around
        # a yield: for word2vec one span is one batch out of
        # SkipGram.batches.
        nonlocal it
        for _ in range(n):
            if it is None:
                return
            with tracing.span("mv.input.next"):
                batch = next(it, dry)
            if batch is dry:
                it = None
                return
            with tracing.span("mv.input.place"):
                queue.append(put(batch))

    enqueue(size)
    while queue:
        yield queue.popleft()
        enqueue(1)
