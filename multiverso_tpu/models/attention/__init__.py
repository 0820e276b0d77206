"""The attention kinds a layer can be, one module a kind.

``KINDS`` maps a ``LayerKind.attn`` name to its ``AttnKind``
(``models/common.py``): its checks of a configuration, its leaves and their
specs, the meshes it refuses, its scope, rotary recipe and saved names, and
its heads.  ``models/transformer.py`` looks a kind up here and knows none by
name.  **A new kind costs**: one file here that ends in its ``AttnKind``, one
line in ``KINDS``, its own sizes as fields of ``TransformerConfig``, and its
kernel under ``ops/``.  The order is the order in which the kinds present in
a model refuse a mesh.

Nothing here imports ``models/transformer.py``; a configuration is read by
attribute."""

from ..common import AttnKind
from .eva import EVA
from .latent import LATENT
from .linear import LINEAR
from .softmax import FULL, NOPE, SLIDING

__all__ = ["KINDS", "AttnKind"]

KINDS = {
    "full_attention": FULL,
    "sliding_attention": SLIDING,
    "latent_attention": LATENT,
    "linear_attention": LINEAR,
    "eva_attention": EVA,
    "full_attention_nope": NOPE,
}
