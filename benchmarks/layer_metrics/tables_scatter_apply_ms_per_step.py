"""``tables.scatter_apply_ms_per_step``: device self time a step under the
scope ``tables.scatter_apply`` (``updaters/base.py:scatter_apply``, both
tables)."""

from benchmarks.trace import program

NAME = "tables.scatter_apply_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "tables"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    return program.scope_ms_per_step(reading, "tables.scatter_apply")
