"""Mixture-of-Experts feed-forward: one router, two schedules.

Not in the reference (a 2016 parameter server predates MoE).  The expert
leaves sit at a layer's top level beside the attention weights (``router
[D, E]``, ``w1``/``w3 [E, D, H]``, ``w2 [E, H, D]``); ``moe_ffn`` reads
those four keys of whatever dict it is given.  Routing is shared and stays
in float32: softmax over the router's logits, top-k, the weights
renormalised or not (``norm_topk_prob``; OLMoE does not), the
load-balancing term ``E * sum_e f_e P_e`` and the router z-loss
``mean_tokens logsumexp(logits)^2``.  What differs is how the ``N*k``
routes reach their experts:

- ``"grouped"``: what a benchmark cell runs
  (``olmoe-1b-7b-e64.zipf-seq4k-b2``).  Dropless: the routes are
  stable-sorted by expert, the rows gathered in that order, three grouped
  matmuls (``jax.lax.ragged_dot``, which XLA:TPU lowers to its own
  grouped-matmul custom call) run over the ``[E, D, H]`` weights with the
  group sizes, the rows are gathered back by the inverse permutation and
  each token sums its ``k`` weighted rows in float32.  The sort order is a
  permutation of the routes, so the transpose of either gather is a gather
  by the other index (``_dispatch``, ``_combine``: hand-written
  ``custom_vjp`` rules): no row moves by scatter, forward or backward.
  Static shapes, FLOPs of exactly the routes, no ``[N*k, E]`` one-hot and
  no capacity.  It does not run over an ``ep`` mesh axis:
  ``transformer_forward`` refuses that by name.
- ``"dense"``: every expert computes every token, scaled afterwards by the
  combine weights.  Exact, ``E/k`` times the useful FLOPs: the oracle the
  tests hold ``grouped`` to, and the one schedule GSPMD partitions over
  ``ep`` (expert-indexed weights carry a ``NamedSharding`` over it and XLA
  turns the einsums into all-to-alls).

Neither drops a route.

Scopes for the chip trace (docs/observability.md, "Chip plane"):
``moe.route``, ``moe.dispatch``, ``moe.experts``, ``moe.combine``; the two
backward rules open ``moe.dispatch`` / ``moe.combine`` themselves, so their
rows are booked where the forward's are.  The schedule a step was traced
with is counted in ``moe.traced{dispatch=}``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import metrics

__all__ = ["GROUPED_SAVED", "init_moe_params", "moe_ffn", "moe_pspecs",
           "moe_shardings"]

# ``checkpoint_name``s of the three grouped-matmul outputs.  A grouped matmul
# is not a ``dot_general``, so remat policy "dots" saves them by name
# (``transformer.py``); without the names the backward runs all three again.
GROUPED_SAVED = ("moe_gate", "moe_up", "moe_down")


def init_moe_params(dim: int, hidden: int, num_experts: int,
                    seed: int = 0) -> Dict[str, Any]:
    rng = np.random.RandomState(seed)

    def w(*shape, scale):
        return (scale * rng.randn(*shape)).astype(np.float32)

    return {
        "router": w(dim, num_experts, scale=0.02),
        "w1": w(num_experts, dim, hidden, scale=dim ** -0.5),   # gate
        "w3": w(num_experts, dim, hidden, scale=dim ** -0.5),   # up
        "w2": w(num_experts, hidden, dim, scale=hidden ** -0.5),
    }


def moe_pspecs(mesh: Mesh) -> Dict[str, Any]:
    """PartitionSpecs: experts shard over ``ep`` when the mesh has one."""
    ep = "ep" if "ep" in mesh.shape else None
    return {
        "router": P(None, None),
        "w1": P(ep, None, None),
        "w3": P(ep, None, None),
        "w2": P(ep, None, None),
    }


def moe_shardings(mesh: Mesh) -> Dict[str, Any]:
    """Experts shard over ``ep`` when the mesh has one; router replicated."""
    return {k: NamedSharding(mesh, s) for k, s in moe_pspecs(mesh).items()}


def _routing(params, x, top_k: int, norm_topk_prob: bool):
    """The shared router, in float32: ``(probs, logits, top_p, top_idx)``
    with ``top_p`` renormalised to sum to 1 over the k routes if asked."""
    logits = (x.astype(jnp.float32)
              @ params["router"].astype(jnp.float32))        # [B,T,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)             # [B,T,k]
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return probs, logits, top_p, top_idx


def _aux_losses(probs, logits, load):
    """``(balance, z)`` of one layer.  ``balance`` is the switch/GShard
    load-balancing term ``E * sum_e f_e P_e``: ``f_e`` the share of tokens
    with a route to expert ``e`` (``load`` counts routes, and a token's k
    routes go to k different experts), ``P_e`` the mean router probability.
    ``z`` is the router z-loss, the mean over tokens of
    ``logsumexp(logits)^2`` (arXiv:2409.02060, section 3.4)."""
    E = probs.shape[-1]
    tokens = probs.size // E
    frac_tokens = load.astype(jnp.float32) / tokens
    frac_prob = jnp.mean(probs.reshape(tokens, E), axis=0)
    balance = E * jnp.sum(frac_tokens * frac_prob)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return balance, z


def moe_ffn(params: Dict[str, Any], x: jax.Array, top_k: int = 2,
            compute_dtype=None, dispatch: str = "dense",
            norm_topk_prob: bool = True
            ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """x [B, T, dim] → ``(out [B, T, dim], balance, z, load [E])``: the
    layer's output, its two auxiliary loss terms (``_aux_losses``, scalars,
    unweighted) and the routes each expert was sent (int32).  ``dispatch``
    picks the schedule (this file's header); the choice is taken at trace
    time and counted in ``moe.traced``."""
    schedules = {"grouped": _moe_grouped, "dense": _moe_dense}
    if dispatch not in schedules:
        raise ValueError(f"unknown moe dispatch '{dispatch}' "
                         "(expected grouped|dense)")
    metrics.counter("moe.traced", {"dispatch": dispatch}).inc()
    return schedules[dispatch](params, x, top_k, compute_dtype or x.dtype,
                               norm_topk_prob)


def _route(params, x, top_k: int, norm_topk_prob: bool):
    """Routing with its losses for the schedules that do not sort:
    ``(top_p, top_idx, balance, z, load)``, the load by ``bincount``."""
    probs, logits, top_p, top_idx = _routing(params, x, top_k,
                                             norm_topk_prob)
    load = jnp.bincount(top_idx.reshape(-1), length=probs.shape[-1]
                        ).astype(jnp.int32)
    return (top_p, top_idx, *_aux_losses(probs, logits, load), load)


def _sort_routes(top_idx):
    """The routes of ``top_idx [N, k]`` stable-sorted by expert (a group keeps
    token order): ``(expert[order], order, inv [N, k])`` with ``inv[order[i]]
    = i`` over the flat routes.  Two sorts, the second of the pairs
    ``(order[i], i)``.  On the v5e a sort of 65,536 routes inside the step is
    0.05-0.07 ms, a gather of as many scalars 0.47; alone, the second sort
    took 0.58-0.62 ms and an int32 scatter of the iota 0.88-0.91 (PERF.md
    section 6, PR 29)."""
    routes = jnp.arange(top_idx.size, dtype=jnp.int32)
    sorted_expert, order = jax.lax.sort((top_idx.reshape(-1), routes),
                                        num_keys=1, is_stable=True)
    _, inv = jax.lax.sort((order, routes), num_keys=1)
    return sorted_expert, order, inv.reshape(top_idx.shape)


@jax.custom_vjp
def _dispatch(x, order, inv):
    """Rows of ``x [N, D]`` in expert order, ``[N*k, D]``: route ``order[i]``
    is a row of token ``order[i] // k``.  ``inv [N, k]`` is where each of a
    token's routes went."""
    return x[order // inv.shape[1]]


def _dispatch_fwd(x, order, inv):
    return _dispatch(x, order, inv), inv


def _dispatch_bwd(inv, d_rows):
    # The transpose of a gather by a permutation is a gather by its
    # inverse: token n's k cotangent rows, summed in float32.
    with jax.named_scope("moe.dispatch"):
        d_x = jnp.sum(d_rows[inv], axis=1, dtype=jnp.float32)
    return d_x.astype(d_rows.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(down, top_p, order, inv, dtype):
    """``out[n] = sum_j top_p[n, j] * down[inv[n, j]]``, summed in float32
    and cast to ``dtype``: the expert-order rows ``down [N*k, D]`` gathered
    back into route order.  The cast is in here so that the transpose is
    handed ``d_out`` as narrow as it was made."""
    return jnp.einsum("nkd,nk->nd", down[inv], top_p,
                      preferred_element_type=jnp.float32).astype(dtype)


def _combine_fwd(down, top_p, order, inv, dtype):
    return _combine(down, top_p, order, inv, dtype), (down, top_p, order, inv)


def _combine_bwd(dtype, res, d_out):
    down, top_p, order, inv = res
    with jax.named_scope("moe.combine"):
        # In expert order, one pass over the rows for both cotangents.
        d_rows = d_out[order // inv.shape[1]].astype(jnp.float32)
        d_down = d_rows * top_p.reshape(-1)[order][:, None]
        d_weight = jnp.sum(d_rows * down.astype(jnp.float32), axis=-1)
    return d_down.astype(down.dtype), d_weight[inv], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _moe_grouped(params, x, top_k, dt, norm_topk_prob):
    B, T, D = x.shape
    N = B * T
    E = params["router"].shape[1]
    with jax.named_scope("moe.route"):
        probs, logits, top_p, top_idx = _routing(params, x, top_k,
                                                 norm_topk_prob)
    with jax.named_scope("moe.dispatch"):
        # Route r = n*k + j is token n's j-th expert.  Sorted by expert, a
        # group's rows are contiguous and its size is the distance between
        # two boundaries.  ``order`` is a permutation of the routes, so rows
        # go out by it and come back by its inverse, and neither way nor
        # either transpose is a scatter.
        sorted_expert, order, inv = _sort_routes(top_idx.reshape(N, top_k))
        bounds = jnp.searchsorted(sorted_expert, jnp.arange(E + 1),
                                  side="left")
        sizes = jnp.diff(bounds).astype(jnp.int32)                # [E]
        rows = _dispatch(x.reshape(N, D).astype(dt), order, inv)  # [N*k, D]
    with jax.named_scope("moe.route"):
        balance, z = _aux_losses(probs, logits, sizes)
    with jax.named_scope("moe.experts"):
        w1, w3, w2 = (checkpoint_name(params[k].astype(dt), "wcast")
                      for k in ("w1", "w3", "w2"))
        gate = checkpoint_name(jax.lax.ragged_dot(rows, w1, sizes),
                               GROUPED_SAVED[0])
        up = checkpoint_name(jax.lax.ragged_dot(rows, w3, sizes),
                             GROUPED_SAVED[1])
        down = checkpoint_name(
            jax.lax.ragged_dot(jax.nn.silu(gate) * up, w2, sizes),
            GROUPED_SAVED[2])                                     # [N*k, D]
    with jax.named_scope("moe.combine"):
        out = _combine(down, top_p.reshape(N, top_k), order, inv, x.dtype)
    return out.reshape(B, T, D), balance, z, sizes


def _moe_dense(params, x, top_k, dt, norm_topk_prob):
    E = params["router"].shape[1]
    top_p, top_idx, balance, z, load = _route(params, x, top_k,
                                              norm_topk_prob)
    # combine [B,T,E]: routing weight per expert (0 for unrouted)
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
        * top_p[..., None], axis=2)

    # dense dispatch: every expert sees every token, scaled post-hoc.
    xc = x.astype(dt)
    gate = jax.nn.silu(jnp.einsum("btd,edh->beth", xc,
                                  params["w1"].astype(dt)))
    up = jnp.einsum("btd,edh->beth", xc, params["w3"].astype(dt))
    expert_out = jnp.einsum("beth,ehd->betd", gate * up,
                            params["w2"].astype(dt))          # [B,E,T,d]
    out = jnp.einsum("betd,bte->btd", expert_out,
                     combine.astype(dt))
    return out.astype(x.dtype), balance, z, load
