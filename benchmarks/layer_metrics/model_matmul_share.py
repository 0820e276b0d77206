"""``model.matmul_share``: share of device busy time in dot/convolution
operations and fusions that hold one, in percent."""

NAME = "model.matmul_share"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    t = reading.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.by_category_s["matmul"] / t.busy_s
