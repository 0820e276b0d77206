"""``model.moe_share``: the share of device busy time under the routed FFN's
four scopes (``moe.route``, ``moe.dispatch``, ``moe.experts``,
``moe.combine``; ``benchmarks/trace/moe.py``), any phase, in percent: whether
the mechanism the cell exists for does most of the work."""

from benchmarks.trace import moe

NAME = "model.moe_share"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"num_experts": True}}


def read(reading):
    return moe.share(reading)
