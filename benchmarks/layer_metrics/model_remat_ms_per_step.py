"""``model.remat_ms_per_step``: the remat tax in time: device self time a step
in instructions JAX recomputes in the backward (``rematted_computation`` in
the ``op_name``)."""

from benchmarks.trace import program

NAME = "model.remat_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return program.phase_ms_per_step(reading, "remat")
