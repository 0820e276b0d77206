"""``model.moe_route_early_ms_per_step``: device self time a step under the
block's scope ``route_early`` (the router's matmul on the attention's normed
input, top-k, the softmax over the chosen, their transposes and, under remat,
the replay), any phase: what choosing before attention costs, booked outside
``attn`` (``benchmarks/trace/route_first.py``)."""

from benchmarks.trace import route_first

NAME = "model.moe_route_early_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_route_first"}


def read(reading):
    return route_first.scope_ms_per_step(reading, "route_early")
