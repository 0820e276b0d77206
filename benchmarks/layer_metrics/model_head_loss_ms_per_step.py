"""``model.head_loss_ms_per_step``: device self time a step under the scopes
``head`` (final norm and the vocabulary matmul) and ``loss`` (the cross-
entropy), forward and backward together."""

from benchmarks.trace import program

NAME = "model.head_loss_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return program.scope_ms_per_step(reading, "head", "loss")
