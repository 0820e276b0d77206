"""``collective.share``: time with a collective in flight or waited for
(all-reduce and kin, sync or async), over the traced window, in percent.
Absent on one chip: there is nothing to read."""

NAME = "collective.share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "collectives"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train", "min_chips": 2}


def read(reading):
    t = reading.trace
    if t is None or t.chips < 2 or t.window_s <= 0:
        return None
    return 100.0 * t.collective_s / t.window_s
