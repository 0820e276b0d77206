"""``kernel.flash_mla_bwd_roofline``: two-width (latent) flash attention's
backward's share of its roofline: what the backward requires a step
(``benchmarks/flops_xing.py:mla_kernel_flops``: 1,280 FLOPs a causal pair,
head and block at 192 / 128, dP 256 + dQ 384 + dV 256 + dK 384; the scores
each kernel rebuilds are recompute) at the bf16 peak, or its least bytes at
the HBM peak, the larger, over ALL device time under calls whose names begin
``flash_mla_bwd``: today the ``_dq`` + ``_dkv`` pair, tomorrow a fused call
(``ops/flash_attention.py``;
``benchmarks/trace/program.py:family_roofline``).  A fused call would issue
1,664 FLOPs for the 1,280 required (77%); the pair issues 2,304 (56%)."""

from benchmarks.trace import program

NAME = "kernel.flash_mla_bwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"kv_lora_rank": True}}


def read(reading):
    f = reading.facts
    return program.family_roofline(
        reading, "flash_mla_bwd",
        f.get("mla_kernel_flops_per_step", {}).get("bwd"),
        f.get("mla_kernel_bytes_per_step", {}).get("bwd"))
