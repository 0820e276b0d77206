"""``apps.batcher_ms_per_step``: ``SkipGram.batches`` alone over the traced
window's corpus slices, host clock, per batch.  It times the layer from
outside; a span inside the program replaces it (the ``tracing`` issue)."""

NAME = "apps.batcher_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "apps"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    return reading.facts.get("batcher_ms_per_step")
