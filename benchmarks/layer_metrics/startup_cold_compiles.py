"""``startup.cold_compiles``: programs compiled from nothing and written to the
persistent cache (counter ``compile.cache{result=miss}``, JAX's
``/jax/compilation_cache/cache_misses``): 0 on a warm start, so the line
itself tells a warm ``setup_s`` from a cold one.  Off the chip (a rehearsal
hands no peaks) what the CPU's cache missed says nothing of a start on the
chip, and the metric is left out."""

from benchmarks import startup

NAME = "startup.cold_compiles"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "compiler"
MOVES = "setup_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    if not reading.peaks or startup.compile_account() is None:
        return None
    from multiverso_tpu import metrics

    return metrics.counter("compile.cache", {"result": "miss"}).value
