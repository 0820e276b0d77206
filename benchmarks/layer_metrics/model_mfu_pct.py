"""``model.mfu_pct``: model-FLOP utilization: the FLOPs the forward and
backward passes of one step require (``benchmarks/flops.py``; recompute not
counted) over the median step time and the chips' published bf16 peak.
``tokens_per_chip_s`` times a constant within a cell."""

NAME = "model.mfu_pct"
UNIT = "%"
BETTER = "higher"
SOURCE = "host_clock"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {}       # every cell that reports the metric it moves


def read(reading):
    f = reading.facts
    if not reading.peaks or f.get("step_s", 0) <= 0:
        return None
    achieved = f["flops_per_step"] / f["step_s"]
    return 100.0 * achieved / (f["chips"] * reading.peaks["bf16_flops_per_s"])
