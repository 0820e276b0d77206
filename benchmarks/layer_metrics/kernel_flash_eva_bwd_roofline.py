"""``kernel.flash_eva_bwd_roofline``: EVA attention's backward pass's share of
its roofline: what a backward requires a step (``benchmarks/flops_eva.py``:
exact pairs x 8 x head_dim FLOPs: dP, dQ, dV, dK; the scores it rebuilds are
recompute) at the bf16 peak, or its least bytes at the HBM peak, the larger,
over ALL device time under names that begin ``flash_eva_bwd``
(``ops/flash_eva.py``; ``benchmarks/trace/eva.py:pass_roofline``).  The fused
kernel issues five matmuls a tile for the four required: ceiling 80%."""

from benchmarks.trace import eva

NAME = "kernel.flash_eva_bwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_eva"}


def read(reading):
    return eva.pass_roofline(reading, "flash_eva_bwd")
