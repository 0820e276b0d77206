"""``kernel.flash_gqa7_bwd_pair_roofline``: the full causal backward pass's
share of its roofline at 7 query heads a K/V head and 16,384 tokens, where
``_fused_fits`` turns the fused call away: two thirds of the triangle's
attention a step requires (dP + dQ, dV + dK) at the bf16 peak, or the pass's
least bytes at the HBM peak, the larger, over ALL device time under names that
begin ``flash_bwd``: the ``flash_bwd_dq`` + ``flash_bwd_dkv`` pair, or the
fused call where one runs (``benchmarks/trace/route_first.py:pass_roofline``).
The pair issues seven matmuls a tile for the four required: at most 57%."""

from benchmarks.trace import route_first

NAME = "kernel.flash_gqa7_bwd_pair_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_route_first"}


def read(reading):
    return route_first.pass_roofline(reading, "flash_bwd", "full_bwd_per_step")
