"""``startup.import_s``: what importing ``multiverso_tpu`` cost the process
(monitor ``mv::import``: ``multiverso_tpu/__init__.py``, first line to last;
jax's own import is outside it, since ``benchmarks/harness.py`` imports jax
first)."""

from benchmarks import startup

NAME = "startup.import_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "startup"
MOVES = "setup_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return startup.monitor_s("mv::import")
