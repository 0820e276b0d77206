"""``model.bwd_ms_per_step``: device self time a step in backward instructions:
``op_name``s that hold a ``transpose(...)`` component and are neither
recomputed nor the update (``trace/program.py:phase``)."""

from benchmarks.trace import program

NAME = "model.bwd_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return program.phase_ms_per_step(reading, "bwd")
