"""``kernel.flash_mla_fwd_roofline``: the two-width flash fwd kernel's share of
its roofline: what the kernel multiplies a step (scores at 192, values at 128,
``benchmarks/flops_xing.py:mla_kernel_flops``) at the bf16 peak, or its least
bytes at the HBM peak, the larger, over the time in the Mosaic call named
``flash_mla_fwd`` (``ops/flash_attention.py``;
``benchmarks/trace/latent.py:kernel_roofline``)."""

from benchmarks.trace import latent

NAME = "kernel.flash_mla_fwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"kv_lora_rank": True}}


def read(reading):
    return latent.kernel_roofline(reading, "flash_mla_fwd")
