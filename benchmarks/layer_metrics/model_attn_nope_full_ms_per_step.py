"""``model.attn_nope_full_ms_per_step``: device self time a step under the
scope ``attn.full_nope`` (the full layers' attention where queries and keys
carry no position embedding: norm, projections, ``flash_fwd``, the
``flash_bwd*`` calls, ``wo``), any phase
(``benchmarks/trace/route_first.py``)."""

from benchmarks.trace import route_first

NAME = "model.attn_nope_full_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_route_first"}


def read(reading):
    return route_first.scope_ms_per_step(reading, "attn.full_nope")
