"""``model.eva_summarise_ms_per_step``: device self time a step under the
scope ``attn.eva.summarise`` (softmax pooling of a chunk's keys and values
into one summary each, forward and its autodiff backward; memory-bound), any
phase (``benchmarks/trace/eva.py``)."""

from benchmarks.trace import eva

NAME = "model.eva_summarise_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_eva"}


def read(reading):
    return eva.scope_ms_per_step(reading, eva.SUMMARISE)
