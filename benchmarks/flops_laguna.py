"""Operations and bytes a train step of a model with layers of different
kinds needs, computed from shapes and from the routes the step counted
(``benchmarks/flops.py`` holds the uniform dense counts and the roofline
arithmetic).  ``model`` is a configuration file's ``model`` group
(``TransformerConfig`` field names): per layer an attention kind
(``layer_types``), its query heads (``heads_per_layer``) and its FFN kind
(``mlp_layer_types``), grouped K/V heads (``n_kv_heads``), a window
(``sliding_window``), a per-head gate, a share of the experts
(``experts_held`` of ``num_experts``) and a shared expert.

What a chip that holds a share of the experts multiplies depends on where
the router sent the tokens, so the routed part is counted from
``held_routes``: the routes that reached experts held here, as the step
returned them (``TransformerTrainer.routes``), never ``tokens * top_k``.
Recompute (remat, the flash backward rebuilding its scores) costs time and
counts nothing, as in ``flops.py``.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

__all__ = ["Kind", "kinds", "attention_matmul_params", "token_matmul_params",
           "attention_pairs", "attention_flops", "flash_kernel_bytes",
           "routed_flops", "grouped_matmul_bytes", "train_flops"]

FULL, SLIDING = "full_attention", "sliding_attention"


class Kind(NamedTuple):
    attn: str
    heads: int
    ffn: str


def kinds(model: dict) -> List[Kind]:
    L = model["n_layers"]
    ffn = "sparse" if model.get("num_experts", 0) else "dense"
    return [Kind((model.get("layer_types") or [FULL] * L)[i],
                 (model.get("heads_per_layer") or [model["n_heads"]] * L)[i],
                 (model.get("mlp_layer_types") or [ffn] * L)[i])
            for i in range(L)]


def _head_dim(model: dict) -> int:
    return model.get("head_dim") or model["dim"] // model["n_heads"]


def attention_matmul_params(model: dict, heads: int) -> int:
    """``wq`` and ``wo`` at ``heads`` query heads, ``wk`` and ``wv`` at the
    K/V heads, the per-head gate."""
    dim, hd = model["dim"], _head_dim(model)
    kv = model.get("n_kv_heads") or heads
    gate = dim * heads if model.get("attn_gate") else 0
    return 2 * dim * heads * hd + 2 * dim * kv * hd + gate


def token_matmul_params(model: dict) -> int:
    """Parameters every token is multiplied with in one forward pass, the
    routed experts left out: the attention projections of every layer by its
    kind, a dense layer's SwiGLU, a sparse layer's router (all columns) and
    shared expert, the output head over the vocabulary held."""
    dim = model["dim"]
    total = model["vocab_size"] * dim
    for k in kinds(model):
        total += attention_matmul_params(model, k.heads)
        if k.ffn == "dense":
            total += 3 * dim * (model.get("dense_hidden") or model["hidden"])
        else:
            total += dim * model["num_experts"]
            total += 3 * dim * model.get("shared_expert_hidden", 0)
    return total


def attention_pairs(seq: int, window: Optional[int] = None) -> int:
    """(query, key) pairs one head scores: causal ``T(T+1)/2``; with a
    window of W keys, ``W(W+1)/2`` for the first W queries and W for each of
    the rest."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_flops(model: dict, batch: int, seq: int, attn: str,
                    backward: bool = True) -> float:
    """QK^T and PV over exactly the visible pairs, all layers of kind
    ``attn``: 2 products x 2 FLOPs x pairs x head_dim a head forward, and
    twice that again backward (dV, dP, dQ, dK)."""
    window = model.get("sliding_window") if attn == SLIDING else None
    heads = sum(k.heads for k in kinds(model) if k.attn == attn)
    forward = 4.0 * batch * heads * attention_pairs(seq, window) * _head_dim(
        model)
    return forward * (3 if backward else 1)


def flash_kernel_bytes(model: dict, batch: int, seq: int, attn: str,
                       dtype_bytes: int = 2) -> dict:
    """Least HBM traffic of flash attention's passes, all layers of kind
    ``attn``: ``{"fwd", "bwd"}`` are what a pass requires whatever calls it
    is made of (``bwd``: q, k, v, o, do and the statistics in, dq, dk, dv
    out); ``{"dq", "dkv"}`` are the split backward kernels' own (each reads
    the operands again), kept for what still quotes them.  Tensors of the
    query heads (q, o, do, dq) and of the K/V heads (k, v, dk, dv) each move
    once; the f32 row statistics are one number a query head and
    position."""
    hd = _head_dim(model)
    q = kv = stats = 0
    for k in kinds(model):
        if k.attn == attn:
            q += batch * k.heads * seq * hd * dtype_bytes
            kv += (batch * (model.get("n_kv_heads") or k.heads) * seq * hd
                   * dtype_bytes)
            stats += batch * k.heads * seq * 4
    return {"fwd": 2 * q + 2 * kv + stats,          # q k v -> o, lse
            "bwd": 4 * q + 4 * kv + stats,          # + o do -> dq dk dv
            "dq": 3 * q + 2 * kv + 2 * stats,       # q k v do lse delta -> dq
            "dkv": 2 * q + 4 * kv + 2 * stats}      # ... -> dk dv


def routed_flops(model: dict, held_routes: float) -> float:
    """FLOPs of one train step's grouped matmuls over ``held_routes`` routes
    (summed over the routed layers): 3 passes x 3 matrices x 2 x routes x
    dim x hidden."""
    return 3 * 3 * 2.0 * held_routes * model["dim"] * model["hidden"]


def grouped_matmul_bytes(model: dict, held_routes: float,
                         dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the same nine grouped matmuls a routed layer:
    each reads its two operands and writes its result once: the held
    experts' weights (or a gradient of their size) and the held routes' rows
    at both widths."""
    d, h = model["dim"], model["hidden"]
    held = model.get("experts_held") or model["num_experts"]
    layers = sum(1 for k in kinds(model) if k.ffn == "sparse")
    return 9.0 * (layers * held * d * h + held_routes * (d + h)) * dtype_bytes


def train_flops(model: dict, batch: int, seq: int,
                held_routes: float) -> float:
    """Model FLOPs of one train step: 6 per matmul parameter and token (2
    forward, 4 backward) for what every token passes, the routed experts
    over the routes counted, attention over its visible pairs by kind."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + routed_flops(model, held_routes)
            + attention_flops(model, batch, seq, FULL)
            + attention_flops(model, batch, seq, SLIDING))
