"""``model.mtp_ms_per_step``: device self time a step anywhere under the scope
``mtp`` (the prediction module: its projection, its own block, its final norm,
the second application of the head and the second cross-entropy), any phase.
Its head and loss are also in ``model.head_loss_ms_per_step``, which books by
the innermost scope."""

from benchmarks.trace import latent

NAME = "model.mtp_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_latent"}


def read(reading):
    return latent.module_ms_per_step(reading)
