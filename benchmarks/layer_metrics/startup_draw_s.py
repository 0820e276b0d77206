"""``startup.draw_s``: the host-side numpy draw of the initial weights
(monitors ``Transformer::init_draw``: ``init_params`` in
``TransformerTrainer.__init__``; ``SkipGram::init_draw``: the input table's
``rng.rand`` and its cast)."""

from benchmarks import startup

NAME = "startup.draw_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "startup"
MOVES = "setup_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return startup.monitor_s("Transformer::init_draw", "SkipGram::init_draw")
