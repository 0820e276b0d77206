"""``model.attn_sliding_ms_per_step``: device self time a step under the scope
``attn.sliding`` (the sliding-window layers' attention: projections, rotary,
the three ``flash_win_*`` kernels, gate), any phase."""

from benchmarks.trace import kinds

NAME = "model.attn_sliding_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_kinds"}


def read(reading):
    return kinds.scope_ms_per_step(reading, "attn.sliding")
