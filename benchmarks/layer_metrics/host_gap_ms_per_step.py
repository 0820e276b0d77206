"""``host.gap_ms_per_step``: device idle time per executed step program,
in milliseconds: what the host path (the benchmark loop, the program's
placer and batcher) costs the device each step.  The ``breakdown`` names
the host activity under the gaps."""

NAME = "host.gap_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "host"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    t = reading.trace
    if t is None or t.step_programs <= 0:
        return None
    return 1e3 * t.idle_s / t.step_programs
