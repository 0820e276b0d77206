"""``startup.compile_or_load_s``: seconds in JAX's
``backend_compile_duration``, one a program: XLA's compile, or the fetch from the persistent cache (monitor
``jax::compile_or_load``, which holds ``jax::cache_load``), every program of
the process."""

from benchmarks import startup

NAME = "startup.compile_or_load_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "compiler"
MOVES = "setup_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    acc = startup.compile_account()
    return acc["compile_or_load_s"] if acc else None
