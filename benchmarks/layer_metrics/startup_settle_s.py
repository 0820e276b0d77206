"""``startup.settle_s``: the passes of the bias rule alone before the window
(monitor ``Transformer::balance_router_bias``, to their completion; its
program's compile or load is inside it, and in ``startup.compile_or_load_s``
too)."""

from benchmarks import startup

NAME = "startup.settle_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "startup"
MOVES = "setup_s"
APPLIES = {"runner": "lm_train_latent"}


def read(reading):
    return startup.monitor_s("Transformer::balance_router_bias")
