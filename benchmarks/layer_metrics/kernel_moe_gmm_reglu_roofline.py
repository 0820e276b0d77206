"""``kernel.moe_gmm_reglu_roofline``: XLA's grouped-matmul calls' share of
their roofline with all 64 experts held: the nine grouped matmuls a layer a
step requires over ``tokens x 6`` routes
(``benchmarks/flops_smallthinker.py``; a forward matmul run again under remat
counts time and no work) at the bf16 peak, or their least bytes at the HBM
peak, the larger, over the time in the compiler's ``ragged-dot`` calls
(``benchmarks/trace/route_first.py:pass_roofline``)."""

from benchmarks.trace import route_first

NAME = "kernel.moe_gmm_reglu_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_route_first"}


def read(reading):
    return route_first.pass_roofline(reading, "ragged-dot", "gmm_per_step")
