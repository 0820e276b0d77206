"""Operations and bytes an algorithm needs, computed from shapes.

The benchmark's yardstick for utilization and roofline shares: what the
forward and backward passes *require*, so recomputation (remat, the flash
backward rebuilding its scores) costs time and counts nothing.  ``model`` is
a configuration file's ``model`` group (``TransformerConfig`` field names).
"""

from __future__ import annotations

__all__ = ["matmul_params", "causal_attention_flops", "lm_train_flops",
           "flash_backward_bytes", "flash_kernel_bytes", "roofline_seconds",
           "sgns_step_bytes"]


def matmul_params(model: dict) -> int:
    """Parameters a token is multiplied with in one forward pass.

    Per layer the four attention projections and the SwiGLU's three
    matrices; with ``num_experts > 0`` the ``top_k`` experts a token is
    routed to plus the router (the *active* parameters, not all experts).
    The output head counts; the embedding is a gather and does not."""
    dim, hidden = model["dim"], model["hidden"]
    mlp = 3 * dim * hidden
    if model.get("num_experts", 0):
        mlp = model.get("top_k", 2) * mlp + dim * model["num_experts"]
    return (model["n_layers"] * (4 * dim * dim + mlp)
            + model["vocab_size"] * dim)


def causal_attention_flops(batch: int, heads: int, seq: int, head_dim: int,
                           backward: bool = True) -> float:
    """QK^T and PV are 2*B*H*T^2*D each; a causal schedule needs half.
    The backward needs four such products (dV, dP, dQ, dK): twice the
    forward.  Rebuilding the scores in the backward is recompute."""
    forward = 2 * (2 * batch * heads * seq * seq * head_dim) / 2
    return forward * (3 if backward else 1)


def lm_train_flops(model: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one train step: 6 per matmul parameter and token
    (2 forward, 4 backward) plus causal attention in every layer."""
    head_dim = model["dim"] // model["n_heads"]
    return (6.0 * matmul_params(model) * batch * seq
            + model["n_layers"] * causal_attention_flops(
                batch, model["n_heads"], seq, head_dim))


def flash_backward_bytes(batch: int, heads: int, seq: int, head_dim: int,
                         dtype_bytes: int = 2) -> float:
    """Least HBM traffic of flash attention's backward, whatever calls it is
    made of: it reads q, k, v, o, do and the f32 row statistics and writes
    dq, dk, dv, each once."""
    tensor = batch * heads * seq * head_dim * dtype_bytes
    return 8 * tensor + batch * heads * seq * 4


def flash_kernel_bytes(batch: int, heads: int, seq: int, head_dim: int,
                       dtype_bytes: int = 2) -> float:
    """Least HBM traffic of flash attention forward and backward: the
    forward reads q, k, v and writes o and the f32 row statistics; the
    backward is ``flash_backward_bytes``."""
    tensor = batch * heads * seq * head_dim * dtype_bytes
    stats = batch * heads * seq * 4
    return (4 * tensor + stats) + flash_backward_bytes(
        batch, heads, seq, head_dim, dtype_bytes)


def roofline_seconds(flops: float, bytes_moved: float, peaks: dict):
    """``(least seconds, "compute" | "memory")``: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s, and which of them it is."""
    compute = flops / peaks["bf16_flops_per_s"]
    memory = bytes_moved / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def sgns_step_bytes(batch: int, negatives: int, dim: int,
                    dtype_bytes: int = 4) -> float:
    """Least HBM traffic of one skip-gram negative-sampling step: every pair
    gathers a centre, a context and ``negatives`` rows, and each of those
    rows is read and written again by the scatter-apply."""
    rows = batch * (2 + negatives)
    return 3.0 * rows * dim * dtype_bytes
