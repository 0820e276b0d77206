"""``kernel.flash_eva_fwd_roofline``: EVA attention's forward pass's share of its
roofline: what the pass requires a step (``benchmarks/flops_eva.py``: exact
pairs, own window + summaries, x 4 x head_dim FLOPs) at the bf16 peak, or its
least bytes at the HBM peak, the larger, over ALL device time under names that
begin ``flash_eva_fwd`` (``ops/flash_eva.py``;
``benchmarks/trace/eva.py:pass_roofline``; under remat "full" the replayed
forward counts time and no work)."""

from benchmarks.trace import eva

NAME = "kernel.flash_eva_fwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_eva"}


def read(reading):
    return eva.pass_roofline(reading, "flash_eva_fwd")
