"""``apps.batcher_span_ms_per_step``: host time inside the window under the
program's span ``mv.input.next`` (``util/prefetch.py``: one pull from
``SkipGram.batches``) per batch delivered (``mv.input.place``).  The pull
that finds the iterator dry has a span and delivers no batch, so this is the
batcher's whole time a step, inside the window (PR 39 retired the metric
that ran the same loop again after the window and timed it from outside)."""

from benchmarks.trace import program

NAME = "apps.batcher_span_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "apps"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    return program.span_ms(reading, "mv.input.next", per="mv.input.place")
