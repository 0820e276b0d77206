"""``kernel.moe_gmm_held_roofline``: XLA's grouped-matmul calls' share of their
roofline where a chip holds a share of the experts, in percent: the nine
grouped matmuls a routed layer over the routes that reached held experts as
the steps counted them, FLOPs or bytes, the larger, over the ``ragged-dot``
calls' time (``benchmarks/trace/kinds.py:gmm_held_roofline``)."""

from benchmarks.trace import kinds

NAME = "kernel.moe_gmm_held_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"experts_held": True}}


def read(reading):
    return kinds.gmm_held_roofline(reading)
