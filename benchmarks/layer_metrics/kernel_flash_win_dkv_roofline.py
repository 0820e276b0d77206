"""``kernel.flash_win_dkv_roofline``: the windowed flash dkv kernel's share of
its roofline: a third of the banded attention a step requires at the bf16
peak, or the kernel's least bytes at the HBM peak, the larger, over the time
in the Mosaic call named ``flash_win_bwd_dkv`` (``ops/flash_attention.py``;
``benchmarks/trace/kinds.py:kernel_roofline``, counts in
``benchmarks/flops_laguna.py``)."""

from benchmarks.trace import kinds

NAME = "kernel.flash_win_dkv_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_kinds"}


def read(reading):
    return kinds.kernel_roofline(reading, "flash_win_bwd_dkv")
