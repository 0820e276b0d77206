"""``startup.place_s``: the weights' way to the device, to its completion
(monitors ``Transformer::init_place``: the leaves' ``device_put`` and the
state's zeros; ``MatrixTable::init_place``: the host buffer at the stored
width, its transfer and the updater's slots, both tables)."""

from benchmarks import startup

NAME = "startup.place_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "startup"
MOVES = "setup_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return startup.monitor_s("Transformer::init_place",
                             "MatrixTable::init_place")
