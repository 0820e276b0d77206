"""``model.attn_linear_ms_per_step``: device self time a step under the scope
``attn.linear`` (linear attention: the five projections, the three short
convolutions, the norms and gates, the decay, and both passes of the chunked
scan of ``ops/kda.py``), any phase (``benchmarks/trace/linear.py``)."""

from benchmarks.trace import linear

NAME = "model.attn_linear_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_linear"}


def read(reading):
    return linear.scope_ms_per_step(reading)
