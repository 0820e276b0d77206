"""``compile.in_window.sgns``: ``compile.in_window`` for the cells whose
throughput is counted in pairs (see ``device_idle_share_sgns.py``)."""

from benchmarks.layer_metrics.compile_in_window import read  # noqa: F401

NAME = "compile.in_window.sgns"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "compiler"
MOVES = "pairs_per_chip_s"
APPLIES = {"runner": "sgns_train"}
