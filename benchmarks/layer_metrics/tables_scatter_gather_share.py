"""``tables.scatter_gather_share``: share of device busy time in gather,
scatter and dynamic-update-slice operations (and fusions of them) of the
fused step, in percent."""

NAME = "tables.scatter_gather_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "tables"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    t = reading.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.by_category_s["scatter_gather"] / t.busy_s
