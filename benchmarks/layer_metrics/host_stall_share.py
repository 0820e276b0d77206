"""``host.stall_share``: the share of the window that ``tokens_per_chip_s``
does not see, in percent: 1 - median step time / mean step time.  The rate
is taken from the median time between two completions, so that a stall of
the shared host or a few slow steps cannot move it; this is what those
stalls and slow steps cost, and a change that makes some steps slow (a
periodic sync, a save) shows here first.  About 0 on a quiet machine, and
under 0 by rounding where the median lies above the mean."""

NAME = "host.stall_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "host_clock"
LAYER = "host"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    f = reading.facts
    if f.get("step_s_mean", 0) <= 0:
        return None
    return 100.0 * (1.0 - f["step_s"] / f["step_s_mean"])
