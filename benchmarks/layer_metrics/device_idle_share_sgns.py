"""``device.idle_share.sgns``: ``device.idle_share`` for the cells whose
throughput is counted in pairs.  A per-layer metric names the one
end-to-end metric it moves, so the same reading has a name per throughput
metric."""

from benchmarks.layer_metrics.device_idle_share import read  # noqa: F401

NAME = "device.idle_share.sgns"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}
