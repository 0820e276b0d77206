"""``SkipGram.batches``: the pairs word2vec trains on, expanded a block of
tokens at a time in numpy (PR 27).  All on the CPU, no compiled step but
the one ``ValueError`` case."""

import numpy as np
import pytest

from multiverso_tpu import tracing

VOCAB, WINDOW, NEGATIVES, BATCH = 50, 4, 3, 16


@pytest.fixture
def sg(mv):
    from multiverso_tpu.apps import SkipGram

    mv.init(updater_type="sgd")
    return SkipGram(VOCAB, 4, negatives=NEGATIVES, window=WINDOW,
                    name="w2v_batches")


def _corpus(n=237, seed=3):
    return np.random.RandomState(seed).randint(VOCAB, size=n).astype(np.int32)


def _equal(a, b):
    return len(a) == len(b) and all(
        np.array_equal(x, y) for p, q in zip(a, b) for x, y in zip(p, q))


def test_pairs_and_their_order_equal_the_plain_loop(sg):
    corpus, seed = _corpus(), 5
    n = corpus.shape[0]
    windows = sg._draw_windows(sg._pair_streams(seed)[0], n)
    assert windows[:WINDOW].max() > 1 and windows[-WINDOW:].max() > 1, \
        "the sample must cut windows at both edges of the corpus"
    centers, contexts = [], []
    for i in range(n):
        w = windows[i]
        for j in range(max(0, i - w), min(n, i + w + 1)):
            if j != i:
                centers.append(corpus[i])
                contexts.append(corpus[j])
    got = list(sg.batches(corpus, BATCH, seed=seed))
    kept = (len(centers) // BATCH) * BATCH
    assert len(got) == kept // BATCH > 0 and kept < len(centers)
    assert np.array_equal(np.concatenate([c for c, _, _ in got]),
                          centers[:kept])
    assert np.array_equal(np.concatenate([o for _, o, _ in got]),
                          contexts[:kept])


def test_one_seed_repeats_and_two_seeds_differ(sg):
    corpus = _corpus()
    first = list(sg.batches(corpus, BATCH, seed=7))
    assert _equal(first, list(sg.batches(corpus, BATCH, seed=7)))
    other = list(sg.batches(corpus, BATCH, seed=8))
    assert not np.array_equal(first[0][2], other[0][2])      # negatives
    assert not _equal([b[:2] for b in first], [b[:2] for b in other])
    # A seed past 32 signed bits, as the benchmark's driver draws them.
    big = list(sg.batches(corpus, BATCH, seed=2 ** 31 + 12345))
    assert _equal(big, list(sg.batches(corpus, BATCH, seed=2 ** 31 + 12345)))


def test_shapes_dtypes_ranges_and_the_dropped_tail(sg):
    corpus = _corpus()
    got = list(sg.batches(corpus.astype(np.int64), BATCH, seed=1))
    assert got
    for c, o, neg in got:
        assert c.shape == o.shape == (BATCH,)
        assert neg.shape == (BATCH, NEGATIVES)
        assert c.dtype == o.dtype == neg.dtype == np.int32
        assert 0 <= neg.min() and neg.max() < VOCAB
    # At most 2 * window pairs a token, so a batch short of one is dropped.
    assert list(sg.batches(corpus[:3], BATCH, seed=1)) == []
    with pytest.raises(ValueError, match="no full batch"):
        sg.train_epoch_fused(corpus[:3], BATCH, seed=1)


@pytest.mark.parametrize("block", [1, 7, 4096])
def test_stream_is_the_same_whatever_the_block(sg, monkeypatch, block):
    from multiverso_tpu.apps import word2vec

    corpus = _corpus()
    want = list(sg.batches(corpus, BATCH, seed=2))
    monkeypatch.setattr(word2vec, "_EXPAND_TOKENS", block)
    assert _equal(want, list(sg.batches(corpus, BATCH, seed=2)))


def _chi_square(counts):
    expected = counts.sum() / counts.shape[0]
    return float(((counts - expected) ** 2 / expected).sum())


def test_windows_and_negatives_are_uniform(sg):
    win_rng, _ = sg._pair_streams(11)
    windows = sg._draw_windows(win_rng, 50_000)
    assert windows.min() == 1 and windows.max() == WINDOW
    # Loose: the 99.9th percentiles of chi-square with 3 and 49 degrees of
    # freedom are 16.3 and 85.4.
    assert _chi_square(np.bincount(windows, minlength=WINDOW + 1)[1:]) < 30
    negatives = np.concatenate([
        neg.reshape(-1) for _, _, neg in sg.batches(
            _corpus(4000), 2048, seed=11)])[:50_000]
    assert negatives.shape[0] == 50_000
    assert _chi_square(np.bincount(negatives, minlength=VOCAB)) < 120


def test_expand_spans_lie_under_the_pulls(sg, monkeypatch):
    from multiverso_tpu.apps import word2vec

    monkeypatch.setattr(word2vec, "_EXPAND_TOKENS", 32)
    corpus = _corpus()
    tracing.disable()
    tracing.clear()
    tracing.enable(rank=0)
    try:
        steps, _ = sg.train_epoch_fused(corpus, BATCH, seed=4)
        events = tracing.events()
    finally:
        tracing.disable()
        tracing.clear()
    by_name = {}
    for e in events:
        by_name.setdefault(e.name, []).append(e)
    expands, pulls = by_name["mv.sgns.expand"], by_name["mv.input.next"]
    assert len(pulls) == steps + 1            # one a batch, and the dry one
    assert len(by_name["mv.input.place"]) == steps
    assert len(expands) == -(-corpus.shape[0] // 32)
    assert sum(e.args["tokens"] for e in expands) == corpus.shape[0]
    for e in expands:                          # each inside one pull
        assert any(p.ts_us <= e.ts_us
                   and e.ts_us + e.dur_us <= p.ts_us + p.dur_us + 1
                   and p.trace_id == e.trace_id for p in pulls)
