"""``kernel.flash_roofline``: the flash kernels' share of their roofline,
in percent: the least time the chip could take for the causal attention a
step requires, forward and backward (``benchmarks/flops.py``: the larger of
FLOPs over the bf16 peak and bytes over the HBM peak), over the time spent
in the Mosaic kernels.  The backward kernel rebuilds the scores: that costs
time and counts no FLOPs.  At head size 128 and these lengths the bound is
compute (about 600 FLOPs a byte at 2048 against the chip's 240)."""

from benchmarks import flops

NAME = "kernel.flash_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train", "model": {"num_experts": False}}


def read(reading):
    t, f = reading.trace, reading.facts
    if t is None or not reading.peaks or t.by_category_s["mosaic"] <= 0:
        return None
    per_chip = t.step_programs / f["chips"]
    least_s, _bound = flops.roofline_seconds(
        f["attention_flops_per_step"] * per_chip,
        f["attention_bytes_per_step"] * per_chip, reading.peaks)
    return 100.0 * least_s / t.by_category_s["mosaic"]
