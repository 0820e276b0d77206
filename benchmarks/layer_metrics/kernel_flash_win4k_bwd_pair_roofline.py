"""``kernel.flash_win4k_bwd_pair_roofline``: the windowed backward pass's
share of its roofline under a 4,096-key window at 16,384 tokens and grouped
heads, where ``_fused_fits`` turns the fused call away: two thirds of the
band's attention a step requires at the bf16 peak, or the pass's least bytes
at the HBM peak, the larger, over ALL device time under names that begin
``flash_win_bwd``: the ``flash_win_bwd_dq`` + ``flash_win_bwd_dkv`` pair, or
the fused call where one runs
(``benchmarks/trace/route_first.py:pass_roofline``)."""

from benchmarks.trace import route_first

NAME = "kernel.flash_win4k_bwd_pair_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_route_first"}


def read(reading):
    return route_first.pass_roofline(reading, "flash_win_bwd",
                                     "win_bwd_per_step")
