"""``device.idle_share``: 1 - (union of device-op intervals / traced
window), in percent, mean over the chips.  Near 0 where the device sets the
pace; what it is not is time the host held the chip back."""

NAME = "device.idle_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    t = reading.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * t.idle_s / t.window_s
