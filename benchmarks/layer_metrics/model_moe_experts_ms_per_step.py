"""``model.moe_experts_ms_per_step``: device self time a step under
``moe.experts``: the three grouped matmuls (forward, their two gradients and,
under remat, the forward again), the gate and the weight casts."""

from benchmarks.trace import moe

NAME = "model.moe_experts_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"num_experts": True}}


def read(reading):
    return moe.scope_ms_per_step(reading, "moe.experts")
