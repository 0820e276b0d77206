"""``model.attn_eva_ms_per_step``: device self time a step under the scope
``attn.eva`` (EVA attention: the four projections, rotary, the chunk
summariser and both passes of ``ops/flash_eva.py``), any phase, kernels
included (``benchmarks/trace/eva.py``)."""

from benchmarks.trace import eva

NAME = "model.attn_eva_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_eva"}


def read(reading):
    return eva.scope_ms_per_step(reading)
