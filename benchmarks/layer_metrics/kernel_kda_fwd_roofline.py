"""``kernel.kda_fwd_roofline``: the chunked scan's forward pass's share of its
roofline: the work of the recurrence as written a step
(``benchmarks/flops_ling.py:kda_flops``) at the bf16 peak, or its least bytes
at the HBM peak, the larger, over ALL device time under the scopes whose names
begin ``kda_fwd`` (``ops/kda.py``; ``benchmarks/trace/linear.py:pass_roofline``;
under remat the forward runs twice and its work counts once)."""

from benchmarks.trace import linear

NAME = "kernel.kda_fwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_linear"}


def read(reading):
    return linear.pass_roofline(reading, "kda_fwd")
