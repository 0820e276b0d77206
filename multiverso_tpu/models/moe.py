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

**A share of the experts.**  ``held=(first, count)`` tells the layer which
experts it holds, as one of the chips that share a layer would be told
(expert parallelism without its exchange): the router keeps every column,
top-k and the renormalisation run over all experts, ``w1``/``w3``/``w2``
hold ``count`` experts, and the layer returns its own experts' part of the
result.  In ``grouped`` the routes to experts held elsewhere are sorted
behind the held groups, so the held routes are the first ``sum(sizes)`` rows
of the order, and **the row buffers are as long as the routes that arrived**:
``route_rungs`` lists the row counts a buffer may take from what a trace sees
(``N*k`` and ``count / E``: twice the even share, and ``N*k``), and
``jax.lax.switch`` runs, on the device, the branch of the smallest rung that
holds this step's held routes (``_held_ffn``).  A branch below the top gathers, multiplies and weights its
first ``C`` rows alone and adds them into their tokens in float32
(``_ffn_first``: a scatter-add of ``C`` rows, cheaper than ``N*k`` row
gathers while ``C`` is a small part of ``N*k``); the top branch is the layer
that holds every expert with its two gathers masked (``_ffn_all``), because a
batch may send every route here and then no smaller buffer is exact.  No
route is dropped at any fill.  Whatever the compiler's grouped matmul leaves
in a buffer's rows beyond its groups, a NaN too, counts exactly zero, forward
and backward (masked, never weighted by zero).  ``_held_ffn`` carries its own
backward: it keeps the layer's inputs and the rung, and builds the rows again
inside the same rung's branch, so no branch's residuals lie beside another's.
``load`` is then ``[count + 1]``: the routes each held expert received, and
last the routes that went elsewhere.  Holding all experts (``held=None``)
traces the program as it was, straight-line.  ``routed_scale`` multiplies the
k weights after the renormalisation; ``shared_expert`` is the always-on
SwiGLU beside the routed ones, under its own scope ``moe.shared``.

Scopes for the chip trace (docs/observability.md, "Chip plane"):
``moe.route``, ``moe.dispatch``, ``moe.experts``, ``moe.combine``,
``moe.shared``; the backward rules open ``moe.dispatch`` /
``moe.combine`` themselves, so their rows are booked where the forward's
are.  The schedule a step was traced with is counted in
``moe.traced{dispatch=}`` (``{dispatch=,scoring=sigmoid}`` where the router
scores by sigmoid), a share in ``moe.held{held=,of=}`` and its ladder in
``moe.route_rows{rungs=,of=}`` (how many rungs, of how many routes); off the
step ``TransformerTrainer.route_rows()`` says which rung each layer took.

**Sigmoid scores and the correction bias** (``scoring="sigmoid"``): the
scores are the logits' sigmoids over all experts, the k experts are the
top of score + ``router_bias``, the weights are the chosen scores
(renormalised, scaled) without the bias.  The bias is a leaf of the layer
that gets no gradient; ``TransformerTrainer`` moves it by rule from the
step's own ``all_load`` (``models/transformer.py:_bias_rule``).

**A choice limited by groups** (``groups=(n_group, topk_group)``): the
experts lie in ``n_group`` groups of adjacent ones, a group's score is the sum
of its two highest (score + bias), and a token chooses its k experts among
those of its ``topk_group`` best groups only (``_kept_groups``).  A share then
sees routes only from the tokens that kept its group: ``moe_ffn`` hands that
count back beside ``load`` (``kept``), and ``moe.traced`` carries the labels
``groups=,kept=``.  ``(1, 1)`` is no limit and traces what it always did.

**A route made elsewhere** (``moe_ffn(route=...)``): ``moe_route`` is the
shared router alone, under ``moe.route``, for a block whose router reads
something other than the rows the experts multiply (the attention sub-layer's
normed input: ``TransformerConfig.router_input``); ``moe_ffn`` then routes
nothing itself, sorts and weighs by what it was handed, and the router's
gradient flows to wherever the route was made.  ``moe.traced`` carries
``router_input=`` there.  **The gate's activation** (``act``): what squashes
the ``w1`` branch of every expert, ``common.GATE_ACTS`` (SwiGLU's ``silu``,
ReGLU's ``relu``); ``moe.traced`` carries ``act=`` where it is not ``silu``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import metrics
from .common import GATE_ACTS, Draw

__all__ = ["GROUPED_SAVED", "init_moe_params", "moe_ffn", "moe_leaves",
           "moe_pspecs", "moe_route", "moe_shardings", "route_rungs",
           "shared_expert"]

# ``checkpoint_name``s of the three grouped-matmul outputs.  A grouped matmul
# is not a ``dot_general``, so remat policy "dots" saves them by name
# (``transformer.py``); without the names the backward runs all three again.
GROUPED_SAVED = ("moe_gate", "moe_up", "moe_down")


def moe_leaves(w: Draw, dim: int, hidden: int, num_experts: int,
               held: int = 0, scoring: str = "softmax") -> Dict[str, Any]:
    """A routed layer's leaves from its ``Draw``: ``held`` experts' weights
    (0 = all) under a router of ``num_experts`` columns; with
    ``scoring="sigmoid"`` the correction bias ``router_bias [num_experts]``
    too, zeros.  Their deviations are their own (the router 0.02, the experts
    fan-in), whatever the model's ``init_std``."""
    held = held or num_experts
    params = {
        "router": w.normal("router", (dim, num_experts), 0.02),
        "w1": w.normal("w1", (held, dim, hidden), dim ** -0.5),   # gate
        "w3": w.normal("w3", (held, dim, hidden), dim ** -0.5),   # up
        "w2": w.normal("w2", (held, hidden, dim), hidden ** -0.5),
    }
    if scoring == "sigmoid":
        params["router_bias"] = np.zeros(num_experts, np.float32)
    return params


def init_moe_params(dim: int, hidden: int, num_experts: int,
                    seed: int = 0, held: int = 0,
                    scoring: str = "softmax") -> Dict[str, Any]:
    """``moe_leaves`` of a layer of its own, drawn from ``seed``."""
    return moe_leaves(Draw(jax.random.key(seed)), dim, hidden, num_experts,
                      held, scoring)


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


def _kept_groups(chosen, groups):
    """``[..., n_group]`` bool: the ``topk_group`` groups a token may choose
    its experts from, ``groups = (n_group, topk_group)``.  The ``E`` scores
    ``chosen`` lie in ``n_group`` groups of adjacent experts; a group's score
    is the sum of its two highest, and the best ``topk_group`` stay
    (arXiv:2412.19437, section 2.1.2's node-limited routing, as the
    ``noaux_tc`` routers of its descendants write it)."""
    n_group, topk_group = groups
    E = chosen.shape[-1]
    if E % n_group or E // n_group < 2 or not 0 < topk_group <= n_group:
        raise ValueError(f"{E} experts do not lie in {n_group} groups of at "
                         f"least two, {topk_group} of them kept")
    grouped = chosen.reshape(*chosen.shape[:-1], n_group, E // n_group)
    score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, best = jax.lax.top_k(score, topk_group)               # [..., kept]
    return jnp.any(best[..., None] == jnp.arange(n_group), axis=-2)


def _routing(params, x, top_k: int, norm_topk_prob: bool,
             routed_scale: float = 1.0, scoring: str = "softmax",
             groups=(1, 1)):
    """The shared router, in float32: ``(probs, logits, top_p, top_idx,
    kept)`` with ``top_p`` renormalised to sum to 1 over the k routes if
    asked, then scaled by ``routed_scale``.  ``scoring="sigmoid"``: ``probs``
    are the logits' sigmoids, the k experts are the top of ``probs +
    router_bias`` (the layer's per-expert correction bias, which a rule moves
    and no gradient does: arXiv:2412.19437, section 2.1.2) and ``top_p`` are
    the chosen experts' ``probs``, the bias left out.  ``groups = (n_group,
    topk_group)`` with more than one group limits a token's choice to the
    experts of its best groups (``_kept_groups``; the others' scores are
    masked to ``-inf`` before the top-k) and ``kept [B, T, n_group]`` says
    which those are; ``(1, 1)`` traces what no limit traces and ``kept`` is
    ``None``."""
    logits = (x.astype(jnp.float32)
              @ params["router"].astype(jnp.float32))        # [B,T,E]
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"unknown router scoring '{scoring}' "
                         "(expected softmax|sigmoid)")
    kept = None
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        chosen = probs + jax.lax.stop_gradient(
            params["router_bias"].astype(jnp.float32))
    else:
        chosen = probs = jax.nn.softmax(logits, axis=-1)
    if groups[0] > 1:
        kept = _kept_groups(jax.lax.stop_gradient(chosen), groups)
        chosen = jnp.where(jnp.repeat(kept, chosen.shape[-1] // groups[0],
                                      axis=-1), chosen, -jnp.inf)
    if chosen is probs:
        top_p, top_idx = jax.lax.top_k(probs, top_k)         # [B,T,k]
    else:
        _, top_idx = jax.lax.top_k(chosen, top_k)
        top_p = jnp.take_along_axis(probs, top_idx, axis=-1)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    if routed_scale != 1.0:
        top_p = top_p * routed_scale
    return probs, logits, top_p, top_idx, kept


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


def _share(params, held):
    """``(first, count)`` of the experts held, or ``None`` when ``held`` is
    all of the router's: checked against the weights, counted once a
    trace."""
    E = params["router"].shape[1]
    count = params["w1"].shape[0]
    first, said = held if held is not None else (0, E)
    if said != count or first < 0 or first + count > E:
        raise ValueError(
            f"held=({first}, {said}) but the layer's weights hold {count} "
            f"experts under a router of {E}")
    if count == E:
        return None
    metrics.counter("moe.held", {"held": str(count), "of": str(E)}).inc()
    return first, count


def moe_route(params: Dict[str, Any], h: jax.Array, top_k: int = 2,
              norm_topk_prob: bool = True, routed_scale: float = 1.0,
              scoring: str = "softmax", groups=(1, 1)):
    """The layer's route from ``h [B, T, dim]`` alone, ``_routing``'s tuple
    under ``moe.route``: what ``moe_ffn(route=...)`` takes where the router
    reads other rows than the experts multiply."""
    with jax.named_scope("moe.route"):
        return _routing(params, h, top_k, norm_topk_prob, routed_scale,
                        scoring, groups)


def moe_ffn(params: Dict[str, Any], x: jax.Array, top_k: int = 2,
            compute_dtype=None, dispatch: str = "dense",
            norm_topk_prob: bool = True, held=None,
            routed_scale: float = 1.0, aux: bool = True,
            scoring: str = "softmax", all_load: bool = False,
            groups=(1, 1), route=None, act: str = "silu",
            router_input: str = ""
            ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                       Optional[jax.Array]]:
    """x [B, T, dim] → ``(out [B, T, dim], balance, z, load, kept)``: the
    layer's output, its two auxiliary loss terms (``_aux_losses``, scalars,
    unweighted; zeros and nothing computed with ``aux=False``) and the
    routes each expert was sent (int32 ``[E]``; with a share of the experts,
    ``held=(first, count)``, ``[count + 1]``: the held experts' and, last,
    the routes that went elsewhere; this file's header; with ``all_load``
    every expert's routes ``[E]`` whatever is held, which is what the bias
    rule reads).  ``dispatch`` picks the schedule and ``scoring`` the
    router's scores (``_routing``); both are taken at trace time and counted
    in ``moe.traced`` (``scoring`` only where it is not softmax, so that the
    counter softmax configurations read keeps its labels).  ``groups =
    (n_group, topk_group)`` limits a token's choice to its best groups
    (``_routing``; labels ``groups=,kept=`` on the counter); ``kept`` is then
    the tokens whose kept groups include the group of the first expert held
    here (of expert 0 where all are held; an int32 scalar), which is what the
    share's routes now hang on, and ``None`` without a limit.  ``route``: a
    route ``moe_route`` made from other rows (``top_k`` and the rest as they
    were given there), in place of one made here from ``x``; ``router_input``
    names those rows on the counter.  ``act``: the experts' gate activation
    (``common.GATE_ACTS``)."""
    schedules = {"grouped": _moe_grouped, "dense": _moe_dense}
    if dispatch not in schedules:
        raise ValueError(f"unknown moe dispatch '{dispatch}' "
                         "(expected grouped|dense)")
    if scoring != "softmax" and aux:
        raise ValueError(f"the auxiliary losses are softmax routing's; "
                         f"scoring='{scoring}' balances by its bias rule "
                         "(aux=False)")
    labels = {"dispatch": dispatch}
    if scoring != "softmax":
        labels["scoring"] = scoring
    if groups[0] > 1:
        labels.update(groups=str(groups[0]), kept=str(groups[1]))
    if act not in GATE_ACTS:
        raise ValueError(f"unknown gate activation '{act}' "
                         f"(expected {'|'.join(GATE_ACTS)})")
    if act != "silu":
        labels["act"] = act
    if route is not None:
        labels["router_input"] = router_input or "given"
    metrics.counter("moe.traced", labels).inc()
    return schedules[dispatch](params, x, top_k, compute_dtype or x.dtype,
                               norm_topk_prob, _share(params, held),
                               routed_scale, aux, scoring, all_load, groups,
                               route, GATE_ACTS[act])


def shared_expert(params: Dict[str, Any], h: jax.Array, dt,
                  act=jax.nn.silu) -> jax.Array:
    """The always-on gated FFN beside the routed experts (``shared_w1``,
    ``shared_w3``, ``shared_w2`` of the layer), unweighted."""
    with jax.named_scope("moe.shared"):
        w1, w3, w2 = (checkpoint_name(params[k].astype(dt), "wcast")
                      for k in ("shared_w1", "shared_w3", "shared_w2"))
        return (act(h @ w1) * (h @ w3)) @ w2


def _all_load(top_idx, E):
    return jnp.bincount(top_idx.reshape(-1), length=E).astype(jnp.int32)


def _count_load(top_idx, E):
    """``_all_load`` by compare and sum: no scatter inside the step."""
    return jnp.sum(top_idx.reshape(-1, 1) == jnp.arange(E), axis=0,
                   dtype=jnp.int32)


def _share_load(load, routes: int):
    """``[count + 1]`` from the held experts' routes ``load [count]``."""
    return jnp.concatenate([load, (routes - jnp.sum(load))[None]])


def _kept_tokens(kept, share, E: int):
    """The tokens whose kept groups (``kept [B, T, n_group]``) include the
    group of the first expert held here; ``None`` without a group limit."""
    if kept is None:
        return None
    group = (share[0] if share is not None else 0) // (E // kept.shape[-1])
    return jnp.sum(kept[..., group], dtype=jnp.int32)


def _sort_routes(key):
    """The routes of ``key [N, k]`` (a route's expert, or with a share its
    place among the held experts and ``count`` for the rest) stable-sorted
    (a group keeps token order): ``(key[order], order, inv [N, k])`` with
    ``inv[order[i]] = i`` over the flat routes.  Two sorts, the second of
    the pairs ``(order[i], i)``.  On the v5e a sort of 65,536 routes inside
    the step is 0.05-0.07 ms, a gather of as many scalars 0.47; alone, the
    second sort took 0.58-0.62 ms and an int32 scatter of the iota 0.88-0.91
    (PERF.md section 6, PR 29)."""
    routes = jnp.arange(key.size, dtype=jnp.int32)
    sorted_key, order = jax.lax.sort((key.reshape(-1), routes),
                                     num_keys=1, is_stable=True)
    _, inv = jax.lax.sort((order, routes), num_keys=1)
    return sorted_key, order, inv.reshape(key.shape)


@jax.custom_vjp
def _dispatch(x, order, inv, mine=None):
    """Rows of ``x [N, D]`` in expert order, ``[N*k, D]``: route ``order[i]``
    is a row of token ``order[i] // k``.  ``inv [N, k]`` is where each of a
    token's routes went; ``mine [N, k]`` (or ``None``: all) marks the routes
    to experts held here, the only ones whose cotangent rows count."""
    return x[order // inv.shape[1]]


def _dispatch_fwd(x, order, inv, mine):
    return _dispatch(x, order, inv, mine), (inv, mine)


def _dispatch_bwd(res, d_rows):
    # The transpose of a gather by a permutation is a gather by its
    # inverse: token n's k cotangent rows, summed in float32.
    inv, mine = res
    with jax.named_scope("moe.dispatch"):
        back = d_rows[inv]
        if mine is not None:
            back = jnp.where(mine[..., None], back, 0)
        d_x = jnp.sum(back, axis=1, dtype=jnp.float32)
    return d_x.astype(d_rows.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine(down, top_p, order, inv, dtype, mine=None):
    """``out[n] = sum_j top_p[n, j] * down[inv[n, j]]``, summed in float32
    and cast to ``dtype``: the expert-order rows ``down [N*k, D]`` gathered
    back into route order.  The cast is in here so that the transpose is
    handed ``d_out`` as narrow as it was made.  With ``mine [N, k]`` the sum
    runs over the routes it marks alone: the rows of the others were never
    computed, and are masked, not weighted by zero (0 x NaN is NaN)."""
    back = down[inv]
    if mine is not None:
        back = jnp.where(mine[..., None], back, 0)
    return jnp.einsum("nkd,nk->nd", back, top_p,
                      preferred_element_type=jnp.float32).astype(dtype)


def _combine_fwd(down, top_p, order, inv, dtype, mine):
    return (_combine(down, top_p, order, inv, dtype, mine),
            (down, top_p, order, inv, mine))


def _combine_bwd(dtype, res, d_out):
    down, top_p, order, inv, mine = res
    with jax.named_scope("moe.combine"):
        # In expert order, one pass over the rows for both cotangents.
        d_rows = d_out[order // inv.shape[1]].astype(jnp.float32)
        weight = top_p if mine is None else jnp.where(mine, top_p, 0)
        d_down = d_rows * weight.reshape(-1)[order][:, None]
        d_weight = jnp.sum(d_rows * down.astype(jnp.float32), axis=-1)
    d_down, d_weight = d_down.astype(down.dtype), d_weight[inv]
    if mine is not None:
        d_weight = jnp.where(mine, d_weight, 0)
    return d_down, d_weight, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def route_rungs(routes: int, count: int, experts: int) -> tuple:
    """The row counts a share's route buffers may take, rising, the last
    ``routes`` (every route held here: the buffers of the layer that holds
    all experts).  From what a trace sees and nothing else: ``routes = N*k``
    and the share ``count / experts``, whose even part of the routes is
    ``routes * count // experts``.  One rung below the last, twice the even
    part: a step's held routes lie under it but for a rare batch, a buffer
    that long costs a tenth more than one cut to the row, and every rung is
    one more branch for the compiler to build, to load at start-up (1.3 s a
    MB of program) and to hold against the memory limit (PERF.md section 6,
    PR 33)."""
    rung = -(-2 * max(routes * count // experts, 1) // 8) * 8    # sublanes
    return (rung, routes) if rung < routes else (routes,)


def _experts(rows, w1, w3, w2, sizes, act=jax.nn.silu):
    """The three grouped matmuls over ``rows`` in expert order, the groups
    ``sizes`` long from row 0; rows beyond the groups are left to chance."""
    gate = checkpoint_name(jax.lax.ragged_dot(rows, w1, sizes),
                           GROUPED_SAVED[0])
    up = checkpoint_name(jax.lax.ragged_dot(rows, w3, sizes),
                         GROUPED_SAVED[1])
    return checkpoint_name(
        jax.lax.ragged_dot(act(gate) * up, w2, sizes),
        GROUPED_SAVED[2])


def _sum_to_tokens(rows, tok, N: int):
    """``out[n]`` = the sum of the float32 ``rows [C, D]`` whose token
    ``tok [C]`` is ``n``: a scatter-add of C rows, which under ~10,000 rows
    costs less than a gather of ``N*k``.  In slices of 512 columns, which
    XLA:TPU's scatter takes faster than whole wide rows (5,120 rows of 3,584:
    1.19 ms for 2.22; of 3,072: 1.03 for 1.14; PERF.md section 6, PR 33)."""
    return jnp.concatenate(
        [jnp.zeros((N, part.shape[1]), jnp.float32).at[tok].add(part)
         for part in jnp.split(rows, list(range(512, rows.shape[1], 512)),
                               axis=1)], axis=1)


@jax.custom_vjp
def _dispatch_first(x, tok, live):
    """``_dispatch`` for the first C rows of the expert order: row ``i`` is
    token ``tok[i]``'s, and ``live [C]`` marks the rows of held routes, the
    only ones whose cotangent counts (masked, summed in float32)."""
    return x[tok]


def _dispatch_first_fwd(x, tok, live):
    return x[tok], (tok, live, x.shape[0])


def _dispatch_first_bwd(res, d_rows):
    tok, live, N = res
    with jax.named_scope("moe.dispatch"):
        d_x = _sum_to_tokens(
            jnp.where(live[:, None], d_rows, 0).astype(jnp.float32), tok, N)
    return d_x.astype(d_rows.dtype), None, None


_dispatch_first.defvjp(_dispatch_first_fwd, _dispatch_first_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _combine_first(down, weight, tok, live, N, dtype):
    """``_combine`` for the first C rows of the expert order: ``out[n]`` is
    the float32 sum of ``weight[i] * down[i]`` over the live rows of token
    ``n``, cast to ``dtype``.  A row that is not live is masked, not weighted
    by zero, forward and backward."""
    rows = down.astype(jnp.float32) * weight[:, None]
    return _sum_to_tokens(jnp.where(live[:, None], rows, 0), tok,
                          N).astype(dtype)


def _combine_first_fwd(down, weight, tok, live, N, dtype):
    return (_combine_first(down, weight, tok, live, N, dtype),
            (down, weight, tok, live))


def _combine_first_bwd(N, dtype, res, d_out):
    down, weight, tok, live = res
    with jax.named_scope("moe.combine"):
        d_rows = d_out[tok].astype(jnp.float32)
        d_down = jnp.where(live[:, None], d_rows * weight[:, None], 0)
        d_weight = jnp.where(
            live, jnp.sum(d_rows * down.astype(jnp.float32), axis=-1), 0)
    return d_down.astype(down.dtype), d_weight, None, None


_combine_first.defvjp(_combine_first_fwd, _combine_first_bwd)


def _ffn_all(dtype, act, floats, ints):
    """A share's routed part over all ``N*k`` rows: the top rung, and the
    layer that holds every expert but for its masks.  ``floats = (x, top_p,
    w1, w3, w2)``, ``ints = (order, inv, mine, sizes)``."""
    (x, top_p, w1, w3, w2), (order, inv, mine, sizes) = floats, ints
    with jax.named_scope("moe.dispatch"):
        rows = _dispatch(x, order, inv, mine)
    with jax.named_scope("moe.experts"):
        down = _experts(rows, w1, w3, w2, sizes, act)
    with jax.named_scope("moe.combine"):
        return _combine(down, top_p, order, inv, dtype, mine)


def _ffn_first(C: int, dtype, act, floats, ints):
    """The same over the first ``C`` rows of the expert order, which hold
    every held route when ``sum(sizes) <= C``: the gathers, the grouped
    matmuls' rows and the elementwise passes are ``C`` long, and the rows
    come back to their tokens by a sum over ``C`` rows, not by ``N*k``
    gathers."""
    (x, top_p, w1, w3, w2), (order, _, _, sizes) = floats, ints
    with jax.named_scope("moe.dispatch"):
        head = order[:C]
        tok = head // top_p.shape[1]
        live = jnp.arange(C) < jnp.sum(sizes)
        rows = _dispatch_first(x, tok, live)
    with jax.named_scope("moe.experts"):
        down = _experts(rows, w1, w3, w2, sizes, act)
    with jax.named_scope("moe.combine"):
        weight = jnp.where(live, top_p.reshape(-1)[head], 0)
        return _combine_first(down, weight, tok, live, x.shape[0], dtype)


def _branches(rungs, dtype, act):
    """One exact way to run a share's routed part a rung: the last over all
    ``N*k`` rows, the others over their first ``C``."""
    return [functools.partial(_ffn_first, C, dtype, act)
            for C in rungs[:-1]] + [functools.partial(_ffn_all, dtype, act)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 4))
def _held_ffn(rungs, dtype, floats, ints, act=jax.nn.silu):
    """A share's routed part, ``out [N, D]``, its row buffers as long as the
    smallest of ``rungs`` that holds this step's held routes (chosen on the
    device: ``jax.lax.switch`` over ``_branches``).  The backward keeps
    ``floats``, ``ints`` and the rung alone and builds the rows again in the
    branch of the same rung, so no branch's residuals lie beside another's."""
    return _held_ffn_fwd(rungs, dtype, floats, ints, act)[0]


def _held_ffn_fwd(rungs, dtype, floats, ints, act=jax.nn.silu):
    rung = jnp.sum(jnp.sum(ints[3]) > jnp.asarray(rungs[:-1], jnp.int32),
                   dtype=jnp.int32)
    out = jax.lax.switch(rung, _branches(rungs, dtype, act), floats, ints)
    return out, (floats, ints, rung)


def _held_ffn_bwd(rungs, dtype, act, res, d_out):
    floats, ints, rung = res

    def transposed(run):
        return lambda floats, ints, d_out: jax.vjp(
            lambda floats: run(floats, ints), floats)[1](d_out)[0]

    return jax.lax.switch(rung, [transposed(run)
                                 for run in _branches(rungs, dtype, act)],
                          floats, ints, d_out), None


_held_ffn.defvjp(_held_ffn_fwd, _held_ffn_bwd)


def _moe_grouped(params, x, top_k, dt, norm_topk_prob, share, routed_scale,
                 aux, scoring="softmax", all_load=False, groups=(1, 1),
                 route=None, act=jax.nn.silu):
    B, T, D = x.shape
    N = B * T
    E = params["router"].shape[1]
    if route is None:
        route = moe_route(params, x, top_k, norm_topk_prob, routed_scale,
                          scoring, groups)
    probs, logits, top_p, top_idx, kept = route
    with jax.named_scope("moe.dispatch"):
        # Route r = n*k + j is token n's j-th expert.  Sorted by expert, a
        # group's rows are contiguous and its size is the distance between
        # two boundaries.  ``order`` is a permutation of the routes, so rows
        # go out by it and come back by its inverse, and neither way nor
        # either transpose is a scatter.  With a share, the routes to the
        # experts held elsewhere sort behind the ``count`` held groups.
        key, mine, groups = top_idx.reshape(N, top_k), None, E
        if share is not None:
            first, groups = share
            mine = (key >= first) & (key < first + groups)
            key = jnp.where(mine, key - first, groups)
        sorted_key, order, inv = _sort_routes(key)
        bounds = jnp.searchsorted(sorted_key, jnp.arange(groups + 1),
                                  side="left")
        sizes = jnp.diff(bounds).astype(jnp.int32)           # [groups]
        x2 = x.reshape(N, D).astype(dt)
        # Holding every expert traces the ops in the order it always did
        # (OLMoE's program is the parent's, byte for byte); a share's gather,
        # matmuls and combine are ``_held_ffn``'s, further down.
        if share is None:
            rows = _dispatch(x2, order, inv)                 # [N*k, D]
    balance = z = jnp.float32(0)
    if aux:
        with jax.named_scope("moe.route"):
            balance, z = _aux_losses(
                probs, logits,
                sizes if share is None else _all_load(top_idx, E))
    with jax.named_scope("moe.experts"):
        w1, w3, w2 = (checkpoint_name(params[k].astype(dt), "wcast")
                      for k in ("w1", "w3", "w2"))
        if share is None:
            down = _experts(rows, w1, w3, w2, sizes, act)    # [N*k, D]
    top_p = top_p.reshape(N, top_k)
    if share is None:
        with jax.named_scope("moe.combine"):
            out = _combine(down, top_p, order, inv, x.dtype)
    else:
        # The held routes are the first sum(sizes) rows of the order: the
        # buffers are cut to the rung that holds them (this file's header).
        rungs = route_rungs(N * top_k, groups, E)
        metrics.counter("moe.route_rows", {"rungs": str(len(rungs)),
                                           "of": str(N * top_k)}).inc()
        out = _held_ffn(rungs, x.dtype, (x2, top_p, w1, w3, w2),
                        (order, inv, mine, sizes), act)
    if all_load:
        with jax.named_scope("moe.route"):
            sizes = _count_load(top_idx, E)
    elif share is not None:
        sizes = _share_load(sizes, N * top_k)
    return (out.reshape(B, T, D), balance, z, sizes,
            _kept_tokens(kept, share, E))


def _moe_dense(params, x, top_k, dt, norm_topk_prob, share, routed_scale,
               aux, scoring="softmax", all_load=False, groups=(1, 1),
               route=None, act=jax.nn.silu):
    E = params["router"].shape[1]
    if route is None:
        route = _routing(params, x, top_k, norm_topk_prob, routed_scale,
                         scoring, groups)
    probs, logits, top_p, top_idx, kept = route
    load = _all_load(top_idx, E)
    balance = z = jnp.float32(0)
    if aux:
        balance, z = _aux_losses(probs, logits, load)
    # combine [B,T,E]: routing weight per expert (0 for unrouted)
    combine = jnp.sum(
        jax.nn.one_hot(top_idx, E, dtype=jnp.float32)
        * top_p[..., None], axis=2)
    if share is not None:
        first, count = share
        combine = combine[..., first:first + count]
        if not all_load:
            load = _share_load(load[first:first + count], top_idx.size)

    # dense dispatch: every (held) expert sees every token, scaled post-hoc.
    xc = x.astype(dt)
    gate = act(jnp.einsum("btd,edh->beth", xc, params["w1"].astype(dt)))
    up = jnp.einsum("btd,edh->beth", xc, params["w3"].astype(dt))
    expert_out = jnp.einsum("beth,ehd->betd", gate * up,
                            params["w2"].astype(dt))          # [B,E,T,d]
    out = jnp.einsum("betd,bte->btd", expert_out,
                     combine.astype(dt))
    return (out.astype(x.dtype), balance, z, load,
            _kept_tokens(kept, share, E))
