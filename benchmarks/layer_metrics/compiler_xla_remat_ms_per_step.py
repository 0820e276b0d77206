"""``compiler.xla_remat_ms_per_step``: device self time a step in the clones
XLA's own rematerialisation pass made (instructions named ``*.remat``,
``*.remat2``, ...), which answers memory pressure by computing a value twice;
0.0 where the step holds none.  A cut across the phases' and the scopes'
metrics, not beside them: a clone keeps the ``op_name`` of what it copies."""

from benchmarks.trace import xla_remat

NAME = "compiler.xla_remat_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "compiler"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return xla_remat.ms_per_step(reading)
