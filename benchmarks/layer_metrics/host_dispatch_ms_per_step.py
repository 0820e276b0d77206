"""``host.dispatch_ms_per_step``: mean length of the program's span
``mv.sgns.dispatch`` inside the window: the host's call of the compiled
step, which returns at once while the runtime's queue has room and otherwise
waits there for the device."""

from benchmarks.trace import program

NAME = "host.dispatch_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "host"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}


def read(reading):
    return program.span_ms(reading, "mv.sgns.dispatch")
