"""Model zoo.

The reference has no model zoo — models live in user scripts (SURVEY.md
§1) — but BASELINE.json's stretch config asks for Llama-style decoder
training through the framework, and this package is where the TPU-native
model layer lives: pure-function transformers with mesh-aware sharding
(data/tensor/sequence parallel) and ring attention for long context.
A ``TransformerConfig`` describes its layers by kind (``LayerKind``:
attention, query heads, FFN), each kind of attention with its own ``Rope``
recipe; ``Layout`` is how the layers are then held (a leading group and a
scanned period).
"""

from .transformer import (LayerKind, Layout, Rope, TransformerConfig,
                          TransformerTrainer, init_params,
                          transformer_forward)

__all__ = ["TransformerConfig", "TransformerTrainer", "init_params",
           "transformer_forward", "Rope", "LayerKind", "Layout"]
