"""``device.unscoped_share.sgns``: ``device.unscoped_share`` for the cells
whose throughput is counted in pairs."""

from benchmarks.trace import program

NAME = "device.unscoped_share.sgns"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    return program.unscoped_share(reading)
