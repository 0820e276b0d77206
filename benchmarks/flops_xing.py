"""Operations and bytes a train step of a latent-attention model with
hyper-connections, sigmoid-routed experts (a share held) and a multi-token
prediction module needs, by hand from shapes and from the routes the step
counted (``benchmarks/flops.py`` holds the roofline arithmetic).  ``model``
is a configuration file's ``model`` group (``TransformerConfig`` field
names).

A block, a token (d = ``dim``, H = ``n_heads``, n = ``hc_mult``):

- latent attention's five projections: ``d r_q + r_q H (d_n + d_r) + d
  (r_kv + d_r) + r_kv H (d_n + d_v) + H d_v d`` parameters
  (``latent_matmul_params``);
- its scores and values over exactly the causal pairs ``T (T + 1) / 2`` a
  head: forward ``2 (d_n + d_r) + 2 d_v`` FLOPs a pair, backward twice that
  (dP, dV at ``d_v``; dQ, dK at ``d_n + d_r``);
- two sets of hyper-connections: the gates' ``n d x (2n + n n)`` matmul
  (parameters, with the projections) and the two mixes' ``n + n n + n``
  multiply-adds a channel, which no matmul unit runs and which are counted
  all the same (``hc_mix_flops``), forward and twice backward;
- a dense SwiGLU, or the router over all experts, the shared expert and the
  routed experts over the routes that reached experts held here as the step
  counted them (``TransformerTrainer.routes``), never ``tokens * top_k``.

The prediction module is one more block of the last layer's kind (its
attention over the same pairs: it runs on all T positions), a ``2d x d``
projection and a second application of the head.  Recompute (remat, the
scores a backward kernel rebuilds) costs time and counts nothing in
``train_flops``, and nothing in a pass's roofline either
(``mla_kernel_flops``: forward 640, backward 1,280 FLOPs a pair and head at
192 / 128: dP 256 + dQ 384 + dV 256 + dK 384).  What the split backward
kernels themselves multiply, the scores each rebuilds included, is 1,024
(dq) and 1,280 (dkv); no metric reads those since PR 39.
"""

from __future__ import annotations

from typing import Dict, List

__all__ = ["ffn_kinds", "blocks", "latent_matmul_params",
           "token_matmul_params", "causal_pairs", "attention_flops",
           "mla_kernel_flops", "mla_kernel_bytes", "hc_mix_flops",
           "routed_flops", "grouped_matmul_bytes", "train_flops"]


def ffn_kinds(model: dict) -> List[str]:
    """The FFN kind of every block, the prediction module's last."""
    default = "sparse" if model.get("num_experts", 0) else "dense"
    kinds = list(model.get("mlp_layer_types")
                 or [default] * model["n_layers"])
    return kinds + kinds[-1:] * int(model.get("mtp_layers", 0))


def blocks(model: dict) -> int:
    return len(ffn_kinds(model))


def latent_matmul_params(model: dict) -> int:
    d, H = model["dim"], model["n_heads"]
    dn, dr, dv = model["qk_nope_dim"], model["qk_rope_dim"], model[
        "v_head_dim"]
    rq, rkv = model["q_lora_rank"], model["kv_lora_rank"]
    return (d * rq + rq * H * (dn + dr) + d * (rkv + dr)
            + rkv * H * (dn + dv) + H * dv * d)


def token_matmul_params(model: dict) -> int:
    """Parameters every token is multiplied with in one forward pass, the
    routed experts left out."""
    d, n = model["dim"], model.get("hc_mult", 0)
    total = model["vocab_size"] * d * (1 + int(model.get("mtp_layers", 0)))
    total += 2 * d * d * int(model.get("mtp_layers", 0))       # the module's
    for ffn in ffn_kinds(model):                               # projection
        total += latent_matmul_params(model)
        total += 2 * n * d * (2 * n + n * n)
        if ffn == "dense":
            total += 3 * d * (model.get("dense_hidden") or model["hidden"])
        else:
            total += d * model["num_experts"]
            total += 3 * d * model.get("shared_expert_hidden", 0)
    return total


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def attention_flops(model: dict, batch: int, seq: int) -> float:
    """Scores and values the step requires, every block: forward ``2 (d_n +
    d_r) + 2 d_v`` a pair and head, backward twice that."""
    pair = 2 * (model["qk_nope_dim"] + model["qk_rope_dim"]) + 2 * model[
        "v_head_dim"]
    return (3.0 * pair * batch * model["n_heads"] * causal_pairs(seq)
            * blocks(model))


def mla_kernel_flops(model: dict, batch: int, seq: int) -> Dict[str, float]:
    """What latent attention's passes require a step, every block: ``{"fwd",
    "bwd"}``; qk = 2 (d_n + d_r), v = 2 d_v a pair and head: forward scores +
    values, backward dP + dV at ``d_v`` and dQ + dK at ``d_n + d_r``, whatever
    calls the backward is made of.  ``{"dq", "dkv"}`` are what each split
    backward kernel multiplies (dq: scores again + dP + dQ; dkv: scores again
    + dV + dP + dK), kept for what still quotes them."""
    qk = 2 * (model["qk_nope_dim"] + model["qk_rope_dim"])
    v = 2 * model["v_head_dim"]
    pairs = (float(batch) * model["n_heads"] * causal_pairs(seq)
             * blocks(model))
    return {"fwd": (qk + v) * pairs, "bwd": (2 * qk + 2 * v) * pairs,
            "dq": (2 * qk + v) * pairs, "dkv": (2 * qk + 2 * v) * pairs}


def mla_kernel_bytes(model: dict, batch: int, seq: int,
                     dtype_bytes: int = 2) -> Dict[str, float]:
    """Least HBM traffic of each pass (``fwd``, ``bwd``) and of each split
    backward kernel (``dq``, ``dkv``), every block: every head's q (d_n +
    d_r), k_n, v, o / do, the one rotated key head, the float32 row
    statistics (one number a head and position) move once."""
    H = model["n_heads"]
    dn, dr, dv = model["qk_nope_dim"], model["qk_rope_dim"], model[
        "v_head_dim"]
    rows = batch * seq
    q, kn, v = (rows * H * w * dtype_bytes for w in (dn + dr, dn, dv))
    kr = rows * dr * dtype_bytes
    stats = rows * H * 4
    one = {"fwd": q + kn + kr + v + v + stats,               # -> o, lse
           # q k_n k_r v o do lse -> dq dk_n dk_r dv
           "bwd": 2 * (q + kn + kr + v) + v + v + stats,
           "dq": q + kn + kr + v + v + 2 * stats + q,        # + do -> dq
           "dkv": q + kn + kr + v + v + 2 * stats + kn + kr + v}
    return {k: float(b) * blocks(model) for k, b in one.items()}


def hc_mix_flops(model: dict, batch: int, seq: int) -> float:
    """The stream mixes' multiply-adds, forward and twice backward: a
    sub-layer reads with n and writes with n n + n a channel."""
    n = model.get("hc_mult", 0)
    per_token = 2 * (n + n * n + n) * model["dim"]
    return 3.0 * per_token * 2 * blocks(model) * batch * seq


def routed_flops(model: dict, held_routes: float) -> float:
    """The grouped matmuls over ``held_routes`` routes (summed over the
    routed blocks): 3 passes x 3 matrices x 2 x routes x dim x hidden."""
    return 3 * 3 * 2.0 * held_routes * model["dim"] * model["hidden"]


def grouped_matmul_bytes(model: dict, held_routes: float,
                         dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the same nine grouped matmuls a routed block:
    the held experts' weights (or a gradient of their size) and the held
    routes' rows at both widths, once each."""
    d, h = model["dim"], model["hidden"]
    held = model.get("experts_held") or model["num_experts"]
    routed = sum(1 for k in ffn_kinds(model) if k == "sparse")
    return 9.0 * (routed * held * d * h + held_routes * (d + h)) * dtype_bytes


def train_flops(model: dict, batch: int, seq: int,
                held_routes: float) -> float:
    """Model FLOPs of one train step: 6 per matmul parameter and token for
    what every token passes, the routed experts over the routes counted,
    attention over its causal pairs, the stream mixes."""
    return (6.0 * token_matmul_params(model) * batch * seq
            + routed_flops(model, held_routes)
            + attention_flops(model, batch, seq)
            + hc_mix_flops(model, batch, seq))
