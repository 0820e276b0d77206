"""``kernel.moe_gmm_roofline``: XLA's grouped-matmul calls' share of their
roofline, in percent (``benchmarks/trace/moe.py:gmm_roofline``; the counts
are ``benchmarks/flops_moe.py``'s).  At OLMoE's sizes the bound is compute."""

from benchmarks.trace import moe

NAME = "kernel.moe_gmm_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train", "model": {"num_experts": True}}


def read(reading):
    return moe.gmm_roofline(reading)
