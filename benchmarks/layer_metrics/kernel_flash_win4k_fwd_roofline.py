"""``kernel.flash_win4k_fwd_roofline``: the windowed forward pass's share of
its roofline under a 4,096-key window: a third of the band's attention a step
requires (``benchmarks/flops_smallthinker.py``: exact pairs of the band) at
the bf16 peak, or the pass's least bytes at the HBM peak, the larger, over ALL
device time under names that begin ``flash_win_fwd``
(``benchmarks/trace/route_first.py:pass_roofline``).  Where the compiler keeps
remat "full"'s replayed forward apart it counts time and no work (at most 50%
then); the cell's one-period scan is unrolled and the replay merges with the
forward (PR 48: 12 Mosaic calls compiled of 16)."""

from benchmarks.trace import route_first

NAME = "kernel.flash_win4k_fwd_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_route_first"}


def read(reading):
    return route_first.pass_roofline(reading, "flash_win_fwd",
                                     "win_fwd_per_step")
