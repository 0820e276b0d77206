"""Traffic for ``lm_train``: every step a fresh ``[batch, seq]`` of token
ids drawn uniformly over the vocabulary from the run's seed.  The stream
depends on the seed alone, so step k sees the same batch in every run."""

from __future__ import annotations

import numpy as np

__all__ = ["batches"]


def batches(traffic: dict, vocab_size: int, seed: int, stream: int = 0):
    """Endless iterator of int32 ``[batch, seq]`` arrays.  ``stream`` picks
    an independent sequence of batches (the reference check uses its own)."""
    rng = np.random.RandomState([seed, stream])
    shape = (int(traffic["batch"]), int(traffic["seq"]))
    while True:
        yield rng.randint(vocab_size, size=shape).astype(np.int32)
