"""Operations and bytes EvaByte's step requires, from shapes
(``benchmarks/flops.py``'s yardstick: what the passes require; recompute
counts nothing).  ``model`` is the configuration file's ``model`` group.

EVA attention (``multiverso_tpu/ops/flash_eva.py``): a query of window ``w``
meets its own window's keys up to itself and ``window // chunk`` summaries of
every earlier window, so a head of ``n = T // W`` windows has exactly

    own      n * W (W + 1) / 2         (+ a shorter last window's)
    summary  W * (W / c) * n (n - 1) / 2

score pairs (``pairs``; ``tests/test_evabyte.py`` counts them against a
brute-force mask).  A pair is two products of ``head_dim`` in the forward
(``q.k`` and ``p v``: 4 D FLOPs, 512 at D = 128) and four in the backward (dP,
dQ, dV, dK: 8 D, 1,024); the scores a backward rebuilds are recompute.
"""

from __future__ import annotations

__all__ = ["pairs", "eva_flops", "eva_bytes", "summarise_flops",
           "matmul_params", "train_flops"]


def pairs(seq: int, window: int, chunk: int) -> dict:
    """Score pairs a head: ``{"own": ., "summary": .}``."""
    n, rest = divmod(seq, window)
    own = n * window * (window + 1) // 2 + rest * (rest + 1) // 2
    per = window // chunk
    # window w's queries (the last may be short) see per * w summaries
    summary = per * (window * n * (n - 1) // 2 + rest * n)
    return {"own": own, "summary": summary}


def _heads_layers(model: dict, batch: int) -> int:
    return batch * model["n_heads"] * model["n_layers"]


def eva_flops(model: dict, batch: int, seq: int) -> dict:
    """``{"fwd": ., "bwd": .}``: what the two passes of the attention require
    a step, every layer."""
    p = pairs(seq, model["eva_window"], model["eva_chunk"])
    of = (p["own"] + p["summary"]) * _heads_layers(model, batch)
    return {"fwd": 4.0 * model["head_dim"] * of,
            "bwd": 8.0 * model["head_dim"] * of}


def eva_bytes(model: dict, batch: int, seq: int, dtype_bytes: int = 2) -> dict:
    """Least HBM traffic of the two passes a step, every layer: the forward
    reads q, k, v and the summaries and writes o and the float32 row
    statistics; the backward reads q, k, v, o, do, the statistics and the
    summaries and writes dq, dk, dv and the summaries' gradients, each once."""
    of = _heads_layers(model, batch)
    tensor = seq * model["head_dim"] * dtype_bytes
    bars = 2 * (seq // model["eva_chunk"]) * model["head_dim"] * dtype_bytes
    stats = seq * 4
    return {"fwd": float(of * (4 * tensor + bars + stats)),
            "bwd": float(of * (8 * tensor + 2 * bars + stats))}


def summarise_flops(model: dict, batch: int, seq: int) -> float:
    """The pooling forward a step: a key's logit (2 D) and its share of the
    two weighted sums (4 D)."""
    return 6.0 * model["head_dim"] * seq * _heads_layers(model, batch)


def matmul_params(model: dict) -> int:
    """Parameters a token is multiplied with in one forward pass: a layer's
    four attention projections and SwiGLU, and the ``n_pred_heads`` heads."""
    dim, width = model["dim"], model["n_heads"] * model["head_dim"]
    return (model["n_layers"] * (4 * dim * width + 3 * dim * model["hidden"])
            + dim * model["n_pred_heads"] * model["vocab_size"])


def train_flops(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one train step: 6 a matmul parameter and token, the
    attention's two passes, the pooling forward and twice that backward."""
    attention = eva_flops(model, batch, seq)
    return (6.0 * matmul_params(model) * batch * seq + attention["fwd"]
            + attention["bwd"] + 3.0 * summarise_flops(model, batch, seq))
