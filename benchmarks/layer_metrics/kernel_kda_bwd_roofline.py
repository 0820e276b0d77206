"""``kernel.kda_bwd_roofline``: the chunked scan's backward pass's share of its
roofline: twice the forward's work of the recurrence as written
(``benchmarks/flops_ling.py:kda_flops``) at the bf16 peak, or its least bytes
at the HBM peak, the larger, over ALL device time under the scopes whose names
begin ``kda_bwd``, kernel or XLA fusion alike (``ops/kda.py``;
``benchmarks/trace/linear.py:pass_roofline``)."""

from benchmarks.trace import linear

NAME = "kernel.kda_bwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_linear"}


def read(reading):
    return linear.pass_roofline(reading, "kda_bwd")
