"""``kernel.flash_share``: share of device busy time in Mosaic custom
calls, in percent.  The flash forward, dq and dkv ``pallas_call``s carry no
``name=``, and the trace names them after the jaxpr, so they are counted
together; the split is the ``tracing`` issue's."""

NAME = "kernel.flash_share"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "kernels"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train"}


def read(reading):
    t = reading.trace
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.by_category_s["mosaic"] / t.busy_s
