"""Operations and bytes a train step needs of a model whose router reads the
attention's own input, whose experts are all held (ReLU-gated, dropless) and
whose layers are full attention without a position embedding among windowed,
rotated ones over grouped K/V heads, computed from shapes
(``benchmarks/flops.py`` holds the roofline arithmetic, ``flops_laguna.py`` the
count of a band's pairs, ``attention_pairs``).  ``model`` is a
configuration file's ``model`` group (``TransformerConfig`` field names).

Counts are of REQUIRED work: the band's and the triangle's visible pairs and
not the tiles a kernel's grid visits, each tensor moved once and not once a
call, so a later kernel is read against the same yardstick whatever
implements it.  Recompute (remat's replay of the forward, a flash backward
rebuilding its scores) costs time and counts nothing, as in ``flops.py``.
With every expert held the routed work is ``tokens x top_k`` rows a layer
whatever the router chose, so nothing here depends on a step's routes.
"""

from __future__ import annotations

from typing import Dict, List, Optional

from benchmarks.flops_laguna import attention_pairs

__all__ = ["NOPE", "SLIDING", "layers_of", "attention_pairs",
           "attention_flops", "flash_bytes", "token_matmul_params", "routes",
           "routed_flops", "grouped_matmul_bytes", "train_flops"]

NOPE, SLIDING = "full_attention_nope", "sliding_attention"


def layers_of(model: dict, attn: Optional[str] = None) -> int:
    """How many layers are of kind ``attn`` (None: all)."""
    kinds: List[str] = list(model["layer_types"])
    return len(kinds) if attn is None else kinds.count(attn)


def _pairs(model: dict, seq: int, attn: str) -> int:
    return attention_pairs(
        seq, model["sliding_window"] if attn == SLIDING else None)


def attention_flops(model: dict, batch: int, seq: int, attn: str,
                    passes: str = "both") -> float:
    """QK^T and PV over exactly the visible pairs of every query head, all
    layers of kind ``attn``: 2 products x 2 FLOPs x head_dim a pair forward
    (``"fwd"``), twice that backward (dV, dP, dQ, dK: ``"bwd"``; the scores
    a backward rebuilds are recompute), or ``"both"``."""
    forward = (4.0 * batch * layers_of(model, attn) * model["n_heads"]
               * _pairs(model, seq, attn) * model["head_dim"])
    return forward * {"fwd": 1, "bwd": 2, "both": 3}[passes]


def flash_bytes(model: dict, batch: int, seq: int, attn: str,
                dtype_bytes: int = 2) -> Dict[str, float]:
    """Least HBM traffic of flash attention's two passes, all layers of kind
    ``attn``, whatever calls a pass is made of: the forward reads q, k, v
    and writes o and the f32 row statistics; the backward reads q, k, v, o,
    do and the statistics and writes dq, dk, dv.  The query heads' tensors
    and the (7 times fewer) K/V heads' each move once."""
    layers, hd = layers_of(model, attn), model["head_dim"]
    q = batch * layers * model["n_heads"] * seq * hd * dtype_bytes
    kv = batch * layers * model["n_kv_heads"] * seq * hd * dtype_bytes
    stats = batch * layers * model["n_heads"] * seq * 4
    return {"fwd": 2 * q + 2 * kv + stats, "bwd": 4 * q + 4 * kv + stats}


def token_matmul_params(model: dict) -> int:
    """Parameters every token is multiplied with in one forward pass, the
    routed experts left out: a layer's four attention projections and its
    router's 64 columns, and the output head over the vocabulary held."""
    dim, hd = model["dim"], model["head_dim"]
    attn = 2 * dim * model["n_heads"] * hd + 2 * dim * model["n_kv_heads"] * hd
    return (layers_of(model) * (attn + dim * model["num_experts"])
            + model["vocab_size"] * dim)


def routes(model: dict, tokens: int) -> int:
    """Rows the grouped matmuls of ONE layer multiply: every token's top_k."""
    return tokens * model["top_k"]


def routed_flops(model: dict, tokens: int) -> float:
    """FLOPs of one train step's grouped matmuls, all layers: 3 passes
    (forward, the rows' gradient, the weights' gradient) x 3 matrices x 2 x
    routes x dim x hidden a layer."""
    return (layers_of(model) * 3 * 3 * 2.0 * routes(model, tokens)
            * model["dim"] * model["hidden"])


def grouped_matmul_bytes(model: dict, tokens: int,
                         dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the same nine grouped matmuls a layer: each reads
    its two operands and writes its result once: all experts' weights (or a
    gradient of their size) and the routes' rows at both widths."""
    d, h = model["dim"], model["hidden"]
    per_matmul = model["num_experts"] * d * h + routes(model, tokens) * (d + h)
    return layers_of(model) * 9.0 * per_matmul * dtype_bytes


def train_flops(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one train step: 6 per matmul parameter and token (2
    forward, 4 backward) for what every token passes, the routed experts
    over ``tokens x top_k`` routes a layer, attention over its visible pairs
    by kind."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + routed_flops(model, batch * seq)
            + attention_flops(model, batch, seq, NOPE)
            + attention_flops(model, batch, seq, SLIDING))
