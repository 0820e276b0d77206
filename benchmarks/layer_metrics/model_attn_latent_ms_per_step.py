"""``model.attn_latent_ms_per_step``: device self time a step under the scope
``attn.latent`` (latent attention: the low-rank projections and their norms,
rotary on the rotated parts, the three ``flash_mla_*`` kernels, the output
projection), any phase, the prediction module's block included."""

from benchmarks.trace import latent

NAME = "model.attn_latent_ms_per_step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "model"
MOVES = "tokens_per_chip_s"
APPLIES = {"model": {"kv_lora_rank": True}}


def read(reading):
    return latent.scope_ms_per_step(reading, "attn.latent")
