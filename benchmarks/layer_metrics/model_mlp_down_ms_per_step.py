"""``model.mlp_down_ms_per_step``: device self time a step under the scope
``mlp.down``: a DENSE FFN sub-layer's ``gated @ w2``, the manual-``tp``
reduction and the residual add.  A routed layer's ``mlp`` has no such scope
(``model.moe_*`` read it): 0.0 in a cell whose FFNs are all routed.

Any phase (forward, backward and replay together), every attention kind of
the cell together; a fusion is booked to its root's ``op_name``
(``benchmarks/trace/parts.py``).  The prediction module's block and the dense
layer 0 of Laguna, Xing and Ling run the same sub-layers, so their parts are
in this sum: a cut across ``model.mtp_ms_per_step``, as that metric is across
``head`` + ``loss``."""

from benchmarks.trace import parts

NAME = "model.mlp_down_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    return parts.part_ms_per_step(reading, "mlp.down")
