"""``model.attn_full_ms_per_step``: device self time a step under the scope
``attn.full`` (the full-attention layers' attention: projections, rotary, the
three ``flash_*`` kernels, gate), any phase, where the layers differ in kind."""

from benchmarks.trace import kinds

NAME = "model.attn_full_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_kinds"}


def read(reading):
    return kinds.scope_ms_per_step(reading, "attn.full")
