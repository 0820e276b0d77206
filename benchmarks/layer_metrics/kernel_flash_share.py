"""``kernel.flash_share``: share of device busy time in the program's own
Mosaic kernels, in percent: every ``tpu_custom_call`` but XLA's grouped
matmul and the row update, which ``reduce.classify`` books as ``matmul``
and ``scatter_gather``.  In the dense cells these are ``flash_fwd`` and
``flash_bwd``; each has a roofline of its own beside this."""

NAME = "kernel.flash_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train", "model": {"num_experts": False}}


def read(reading):
    t = reading.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.by_category_s["mosaic"] / t.busy_s
