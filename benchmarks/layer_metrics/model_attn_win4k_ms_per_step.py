"""``model.attn_win4k_ms_per_step``: device self time a step under the scope
``attn.sliding`` (the windowed, rotated layers' attention: norm, projections,
rotary, ``flash_win_fwd``, the ``flash_win_bwd*`` calls, ``wo``), any phase,
in a model whose router chooses before attention
(``benchmarks/trace/route_first.py``)."""

from benchmarks.trace import route_first

NAME = "model.attn_win4k_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_route_first"}


def read(reading):
    return route_first.scope_ms_per_step(reading, "attn.sliding")
