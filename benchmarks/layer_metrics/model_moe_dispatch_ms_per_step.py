"""``model.moe_dispatch_ms_per_step``: device self time a step under
``moe.route`` (router, top-k, losses) + ``moe.dispatch`` (sort, group sizes,
gather) + ``moe.combine`` (weighting, scatter-add): what a change of the
dispatch moves."""

from benchmarks.trace import moe

NAME = "model.moe_dispatch_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"num_experts": True}}


def read(reading):
    return moe.scope_ms_per_step(reading, "moe.route", "moe.dispatch",
                                 "moe.combine")
