"""``kernel.flash_win_bwd_roofline``: windowed flash attention's backward's
share of its roofline: two thirds of the banded attention a step requires
(``benchmarks/flops_laguna.py``: exact pair count) at the bf16 peak, or its
least bytes at the HBM peak, the larger, over ALL device time under calls
whose names begin ``flash_win_bwd``: the fused call or the ``_dq`` + ``_dkv``
pair (``ops/flash_attention.py``;
``benchmarks/trace/program.py:family_roofline``).  At most 80%: a fused call
issues five matmuls a tile for the four required."""

from benchmarks.trace import program

NAME = "kernel.flash_win_bwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_kinds"}


def read(reading):
    f = reading.facts
    work = f.get("sliding_attention_flops_per_step")
    return program.family_roofline(
        reading, "flash_win_bwd", None if work is None else 2 * work / 3,
        f.get("sliding_kernel_bytes_per_step", {}).get("bwd"))
