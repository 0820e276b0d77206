"""Traffic for ``sgns_train``: a Zipf-distributed token stream folded
modulo the vocabulary, cut into chunks of a fixed number of tokens.

Began as a copy of ``multiverso_tpu/apps/word2vec.py:synthetic_corpus``
(which the app keeps as its own stand-in for text8) and is the benchmark's
own since: a later change to the program cannot move the traffic."""

from __future__ import annotations

import numpy as np

__all__ = ["corpus", "chunks"]


def corpus(traffic: dict, vocab_size: int, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    ranks = rng.zipf(float(traffic["zipf_a"]),
                     size=int(traffic["corpus_tokens"]))
    return ((ranks - 1) % vocab_size).astype(np.int32)


def chunks(tokens: np.ndarray, chunk_tokens: int):
    """Endless iterator of consecutive ``chunk_tokens``-long slices,
    starting over when the corpus is used up."""
    n = (tokens.shape[0] // chunk_tokens) * chunk_tokens
    if n == 0:
        raise ValueError(f"corpus of {tokens.shape[0]} tokens holds no chunk "
                         f"of {chunk_tokens}")
    while True:
        for a in range(0, n, chunk_tokens):
            yield tokens[a:a + chunk_tokens]
