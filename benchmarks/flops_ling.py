"""Operations and bytes a train step of a model of linear-attention (Kimi
Delta Attention) layers beside latent ones needs, with sigmoid-routed experts
(a share held), by hand from shapes and from the routes the step counted
(``benchmarks/flops.py`` holds the roofline arithmetic).  ``model`` is a
configuration file's ``model`` group (``TransformerConfig`` field names).

A token (d = ``dim``, H = ``n_heads``, D = ``head_dim``):

- a linear layer's projections: ``wq``, ``wk``, ``wv``, ``wf`` and ``wo`` at
  ``d x H D``, ``wb`` and the head-wise gate ``wg`` at ``d x H``
  (``linear_matmul_params``); its three convolutions of ``linear_conv_kernel``
  taps over ``H D`` channels, multiply-adds no matmul unit runs and counted all
  the same;
- **its scan: the work of the recurrence as written, whatever implements
  it** (``kda_flops``): a head and token, forward, the decay of the ``D x D``
  state (``D^2`` multiplies), ``k^T S``, the rank-one update and ``S^T q`` (2
  ``D^2`` each): ``7 D^2``; backward twice that.  A chunked form spends more
  (the triangular solve, the products with the chunk's state) and that is its
  own cost, not the model's;
- a latent layer without a query latent: ``d H (d_n + d_r) + d (r_kv + d_r)
  + r_kv H (d_n + d_v) + H d_v d`` and the gate's ``d H``; its scores and
  values over the causal pairs, as ``flops_xing.py`` counts them;
- a dense SwiGLU, or the router over all experts, the shared expert and the
  routed experts over the routes that reached experts held here as the step
  counted them (``TransformerTrainer.routes``), never ``tokens * top_k``.

Recompute (remat, what a backward pass rebuilds) costs time and counts
nothing in ``train_flops``.  The scan's least bytes (``kda_bytes``): q, k, v,
the log-decay (float32), beta and o once, the state that enters each chunk
once each way; backward the same inputs, the states, ``do`` and the five
gradients.  The larger of FLOPs at the bf16 peak and bytes at the HBM peak is
its roofline.
"""

from __future__ import annotations

from typing import Dict, List

from benchmarks import flops_xing
from benchmarks.flops_xing import grouped_matmul_bytes, routed_flops

__all__ = ["layer_kinds", "linear_matmul_params", "latent_matmul_params",
           "token_matmul_params", "attention_flops", "mla_kernel_flops",
           "mla_kernel_bytes", "kda_flops", "kda_bytes", "conv_flops",
           "routed_flops", "grouped_matmul_bytes", "train_flops", "CHUNK"]

CHUNK = 64            # tokens between two kept states (ops/kda.py)
LINEAR, LATENT = "linear_attention", "latent_attention"


def layer_kinds(model: dict) -> List[tuple]:
    """``(attention kind, FFN kind)`` of every layer."""
    L = model["n_layers"]
    default = "sparse" if model.get("num_experts", 0) else "dense"
    return list(zip(model["layer_types"],
                    model.get("mlp_layer_types") or [default] * L))


def _count(model: dict, attn: str) -> int:
    return sum(1 for a, _ in layer_kinds(model) if a == attn)


def _gate(model: dict) -> int:
    return model["dim"] * model["n_heads"] if model.get("attn_gate") else 0


def linear_matmul_params(model: dict) -> int:
    d, H = model["dim"], model["n_heads"]
    return 5 * d * H * model["head_dim"] + d * H + _gate(model)


def latent_matmul_params(model: dict) -> int:
    d, H = model["dim"], model["n_heads"]
    dn, dr, dv = model["qk_nope_dim"], model["qk_rope_dim"], model[
        "v_head_dim"]
    rkv = model["kv_lora_rank"]
    return (d * H * (dn + dr) + d * (rkv + dr) + rkv * H * (dn + dv)
            + H * dv * d + _gate(model))


def token_matmul_params(model: dict) -> int:
    """Parameters every token is multiplied with in one forward pass, the
    routed experts left out."""
    d = model["dim"]
    total = model["vocab_size"] * d
    for attn, ffn in layer_kinds(model):
        total += (linear_matmul_params(model) if attn == LINEAR
                  else latent_matmul_params(model))
        if ffn == "dense":
            total += 3 * d * (model.get("dense_hidden") or model["hidden"])
        else:
            total += d * model["num_experts"]
            total += 3 * d * model.get("shared_expert_hidden", 0)
    return total


def _latent_only(model: dict) -> dict:
    """The latent layers alone, as ``flops_xing.py`` counts blocks: its
    attention arithmetic (exact causal pairs, 640 FLOPs a pair and head
    forward and 1,280 backward, their least bytes) holds for them."""
    n = _count(model, LATENT)
    return dict(model, n_layers=n, mlp_layer_types=["dense"] * n,
                mtp_layers=0)


def attention_flops(model: dict, batch: int, seq: int) -> float:
    """Scores and values the latent layers require: forward ``2 (d_n + d_r)
    + 2 d_v`` a pair and head, backward twice that."""
    return flops_xing.attention_flops(_latent_only(model), batch, seq)


def mla_kernel_flops(model: dict, batch: int, seq: int) -> Dict[str, float]:
    """What latent attention's passes require a step over the latent layers
    (one, here): ``flops_xing.mla_kernel_flops``."""
    return flops_xing.mla_kernel_flops(_latent_only(model), batch, seq)


def mla_kernel_bytes(model: dict, batch: int, seq: int) -> Dict[str, float]:
    """Least HBM traffic of those passes over the latent layers."""
    return flops_xing.mla_kernel_bytes(_latent_only(model), batch, seq)


def kda_flops(model: dict, batch: int, seq: int) -> Dict[str, float]:
    """The recurrence's work over the linear layers: ``{"fwd", "bwd"}``."""
    D = model["head_dim"]
    fwd = (float(batch) * seq * model["n_heads"] * 7 * D * D
           * _count(model, LINEAR))
    return {"fwd": fwd, "bwd": 2 * fwd}


def kda_bytes(model: dict, batch: int, seq: int,
              dtype_bytes: int = 2) -> Dict[str, float]:
    """The scan's least HBM traffic over the linear layers."""
    H, D = model["n_heads"], model["head_dim"]
    rows = float(batch) * seq * H
    narrow, decay, beta = rows * D * dtype_bytes, rows * D * 4, rows * 4
    states = float(batch) * H * -(-seq // CHUNK) * D * D * 4
    inputs = 3 * narrow + decay + beta                  # q k v, g, beta
    one = {"fwd": inputs + narrow + states,             # -> o, states
           "bwd": inputs + states + narrow + inputs}    # + do -> 5 gradients
    return {k: b * _count(model, LINEAR) for k, b in one.items()}


def conv_flops(model: dict, batch: int, seq: int) -> float:
    """The three short convolutions' multiply-adds, forward and twice
    backward."""
    per_token = (3 * 2 * model.get("linear_conv_kernel", 4)
                 * model["n_heads"] * model["head_dim"])
    return 3.0 * per_token * batch * seq * _count(model, LINEAR)


def train_flops(model: dict, batch: int, seq: int,
                held_routes: float) -> float:
    """Model FLOPs of one train step: 6 per matmul parameter and token for
    what every token passes, the routed experts over the routes counted, the
    latent layers' attention over its causal pairs, the linear layers'
    recurrence and convolutions."""
    scan = kda_flops(model, batch, seq)
    return (6.0 * token_matmul_params(model) * batch * seq
            + routed_flops(model, held_routes)
            + attention_flops(model, batch, seq)
            + scan["fwd"] + scan["bwd"] + conv_flops(model, batch, seq))
