"""``model.fwd_ms_per_step``: device self time a step in forward instructions:
``op_name``s that went through ``jvp`` and neither ``transpose`` nor a
recompute (``trace/program.py:phase``), under whichever scope."""

from benchmarks.trace import program

NAME = "model.fwd_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return program.phase_ms_per_step(reading, "fwd")
