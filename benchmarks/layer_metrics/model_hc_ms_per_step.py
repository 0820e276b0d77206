"""``model.hc_ms_per_step``: device self time a step under the scopes
``hc.gates`` (the streams' norm, the gates' matmul, sigmoids, Sinkhorn) and
``hc.mix`` (reading a sub-layer's input from the n streams and writing its
output back into them), any phase, the prediction module's block included."""

from benchmarks.trace import latent

NAME = "model.hc_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"runner": "lm_train_latent"}


def read(reading):
    return latent.scope_ms_per_step(reading, "hc.gates", "hc.mix")
