"""``kernel.flash_bwd_roofline``: flash attention's backward's share of its
roofline: two thirds of the causal attention a step requires (dP + dQ and dV
+ dK; the scores a backward rebuilds are recompute) at the bf16 peak, or its
least bytes at the HBM peak, the larger, over ALL device time under calls
whose names begin ``flash_bwd``: the fused call (``ops/flash_attention.py``,
since PR 35) or the ``flash_bwd_dq`` + ``flash_bwd_dkv`` pair where the
program splits it (``benchmarks/trace/program.py:family_roofline``).  A fused
call issues five matmuls a tile for the four required, so it cannot pass 80%;
a split pair issues seven."""

from benchmarks.trace import program

NAME = "kernel.flash_bwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": ("lm_train", "lm_train_kinds")}


def read(reading):
    f = reading.facts
    work = f.get("attention_flops_per_step")
    return program.family_roofline(
        reading, "flash_bwd", None if work is None else 2 * work / 3,
        f.get("attention_bwd_bytes_per_step"))
