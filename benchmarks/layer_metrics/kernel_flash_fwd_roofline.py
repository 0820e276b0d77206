"""``kernel.flash_fwd_roofline``: the flash forward kernel's share of its
compute roofline: a third of the causal attention a step requires at the
bf16 peak, over the time in the Mosaic call named ``flash_fwd``
(``ops/flash_attention.py``)."""

from benchmarks.trace import program

NAME = "kernel.flash_fwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": ("lm_train", "lm_train_kinds")}


def read(reading):
    return program.kernel_roofline(reading, "flash_fwd")
