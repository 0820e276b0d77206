"""``device.unscoped_share``: share of device busy time in instructions the
program's names do not reach: no ``op_name``, or one with neither a phase
nor a scope (``trace/program.py:unscoped``); compiler-made copies and
prefetches mostly."""

from benchmarks.trace import program

NAME = "device.unscoped_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return program.unscoped_share(reading)
