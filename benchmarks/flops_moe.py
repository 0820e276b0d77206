"""Operations and bytes the grouped expert matmuls of a routed FFN need,
computed from shapes (``benchmarks/flops.py`` holds the dense counts and the
roofline arithmetic).  ``model`` is a configuration file's ``model`` group.

A token is sent to ``top_k`` experts, so a layer has ``tokens * top_k``
routes, and each route is multiplied with its expert's three matrices
(``dim x hidden`` twice, ``hidden x dim`` once).  A train step passes each
matrix three times: forward, the gradient of the rows, the gradient of the
weights.  Running a forward matmul again in the backward is recompute: it
costs time and counts nothing."""

from __future__ import annotations

__all__ = ["routes", "grouped_matmul_flops", "grouped_matmul_bytes"]


def routes(model: dict, tokens: int) -> int:
    return tokens * model["top_k"]


def grouped_matmul_flops(model: dict, tokens: int) -> float:
    """FLOPs of one train step's grouped matmuls, all layers: 3 passes x 3
    matrices x 2 x routes x dim x hidden a layer."""
    per_layer = (3 * 3 * 2.0 * routes(model, tokens)
                 * model["dim"] * model["hidden"])
    return model["n_layers"] * per_layer


def grouped_matmul_bytes(model: dict, tokens: int,
                         dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the same nine grouped matmuls, all layers, at
    ``dtype_bytes`` an element: every matmul reads its two operands and
    writes its result once.  Forward and row-gradient passes move all
    experts' weights (``E x dim x hidden``) and the routes' rows at both
    widths; the weight-gradient pass reads the rows at both widths and
    writes a gradient the size of the weights."""
    r = routes(model, tokens)
    d, h = model["dim"], model["hidden"]
    weight = model["num_experts"] * d * h
    rows = r * (d + h)
    per_matmul = weight + rows
    return model["n_layers"] * 9.0 * per_matmul * dtype_bytes
