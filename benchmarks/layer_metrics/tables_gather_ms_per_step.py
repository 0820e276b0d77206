"""``tables.gather_ms_per_step``: device self time a step under the scope
``tables.gather`` (the three row gathers of ``SkipGram.make_fused_step``).
A layout copy counts here only if its ``op_name`` holds the scope."""

from benchmarks.trace import program

NAME = "tables.gather_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "tables"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    return program.scope_ms_per_step(reading, "tables.gather")
