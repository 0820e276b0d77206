"""``collective.exposed_share``: the part of ``collective.share`` during
which no compute operation runs on that chip: communication the step waits
for, in percent of the traced window."""

NAME = "collective.exposed_share"
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
    return 100.0 * t.collective_exposed_s / t.window_s
