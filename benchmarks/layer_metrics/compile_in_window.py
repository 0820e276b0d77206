"""``compile.in_window``: programs JAX compiled, or fetched from its
compile cache, inside the measured window (JAX's own
``backend_compile_duration`` events).  Must be 0: every shape is warmed up
during set-up, and a run where it is not is not ``correct``."""

NAME = "compile.in_window"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "compiler"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return float(reading.compiles_in_window)
