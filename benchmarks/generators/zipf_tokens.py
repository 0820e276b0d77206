"""Traffic for ``lm_train``: every step a fresh ``[batch, seq]`` of token
ids whose frequencies follow Zipf's law, as the tokens of natural text do:
rank r of the vocabulary is drawn with probability proportional to
``r ** -zipf_exponent`` (the traffic file's key; 1.0 where it is absent),
and a permutation seeded by the run decides which id holds which rank.  A
few ids are then most of a batch, so a router sends a few experts far more
rows than the rest; uniform ids (``uniform_tokens``) would spread them
evenly.  The stream depends on the seed alone, so step k sees the same batch
in every run."""

from __future__ import annotations

import numpy as np

__all__ = ["batches"]


def batches(traffic: dict, vocab_size: int, seed: int, stream: int = 0):
    """Endless iterator of int32 ``[batch, seq]`` arrays.  ``stream`` picks
    an independent sequence of batches (the reference check uses its own)
    over the same assignment of ids to ranks."""
    exponent = float(traffic.get("zipf_exponent", 1.0))
    shape = (int(traffic["batch"]), int(traffic["seq"]))
    weights = np.arange(1, vocab_size + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(weights / weights.sum())
    id_of_rank = np.random.RandomState([seed, 2 ** 31 - 1]).permutation(
        vocab_size).astype(np.int32)
    rng = np.random.RandomState([seed, stream])
    while True:
        ranks = np.searchsorted(cdf, rng.random_sample(shape), side="right")
        yield id_of_rank[np.minimum(ranks, vocab_size - 1)]
