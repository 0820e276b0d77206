"""``updater.ms_per_step``: device self time a step under the scope ``update``
(``TransformerTrainer._apply_updates``: the updater over every parameter
leaf)."""

from benchmarks.trace import program

NAME = "updater.ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "updaters"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return program.phase_ms_per_step(reading, "update")
