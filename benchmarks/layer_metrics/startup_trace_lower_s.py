"""``startup.trace_lower_s``: seconds JAX spent tracing functions and lowering
them to MLIR modules (the compile account's ``trace_s`` + ``lower_s``: monitors
``jax::trace`` + ``jax::lower``), every program of the process."""

from benchmarks import startup

NAME = "startup.trace_lower_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "compiler"
MOVES = "setup_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    acc = startup.compile_account()
    return acc["trace_s"] + acc["lower_s"] if acc else None
