"""Llama-style decoder-only transformer, TPU-first.

Design (not in the reference — see models/__init__):

- pure functions over a params pytree; everything jits;
- **bfloat16 compute, float32 params/state** — the MXU-friendly recipe;
- mesh-aware: batch shards over ``dp``, attention heads + MLP hidden +
  vocab shard over ``tp`` (GSPMD inserts the collectives), sequence shards
  over ``sp`` with ring attention (``parallel/ring_attention.py``);
- layers of different kinds (``LayerKind``: an attention kind with its own
  query heads, a dense or routed FFN that may hold a share of the experts
  beside a shared one; a per-head output gate), held as ``Layout`` writes
  them: a leading group, a period whose slots are stacked over its
  repetitions and scanned (a long run of consecutive slots alike as one inner
  scan: ``Layout.runs``), a trailing part.  Every layer alike is the one-slot
  case.  **An attention kind is one module under ``attention/``**
  (``attention.KINDS``: full and sliding softmax attention, full attention
  without a position embedding, latent, linear, EVA) that owns its checks,
  leaves, specs, mesh refusals, scope, rotary recipe and heads; this file
  looks a kind up there and names none.  A new
  kind is added in four steps: a file under ``attention/`` ending in its
  ``AttnKind``, a line in ``KINDS``, its sizes as fields of
  ``TransformerConfig``, its kernel under ``ops/``;
- ``_forward`` is embed -> layers (``_run_stack``, or ``_run_pipeline`` over
  ``pp``) -> (``_mtp``) -> head over module-level parts that take a ``Ctx``
  (``common.py``: dtypes, mesh, the norm gain, the residual sum); a layer is
  ``_make_block``'s ``block`` = ``_attn_sub`` + ``_mlp_sub``, each norming
  its own input; where the router reads the attention's (``router_input``)
  the block norms that once, routes from it under ``route_early`` before the
  heads are made and hands the route on to the FFN;
- a residual of ``hc_mult`` streams mixed by hyper-connections
  (``_hc_gates``, ``_hc_read``, ``_hc_write``), and a multi-token-prediction
  module (``mtp_layers``), both off by default; a residual stream wider in
  precision than the sub-layers' compute (``residual_dtype``), norm gains
  stored as offsets from one (``norm_unit_offset``) and ``n_pred_heads``
  parallel heads predicting the next tokens, all off by default too;
- updater integration: the train step applies the framework's server-side
  updaters (SURVEY.md §2.16) per parameter leaf, so a Multiverso user's
  ``-updater_type`` flag means the same thing here.
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass, replace
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..updaters import AddOption, get_updater
from .. import dashboard, metrics, tracing
from .attention import KINDS
from .common import (GATE_ACTS, Ctx, Draw, Rope, elem, head_spread, proj,
                     rms_norm, unit_gain)
from .moe import (GROUPED_SAVED, moe_ffn, moe_leaves, moe_pspecs, moe_route,
                  route_rungs, shared_expert)

__all__ = ["TransformerConfig", "Rope", "LayerKind", "Layout", "init_params",
           "stack_layer_params", "transformer_forward", "expert_load",
           "TransformerTrainer"]

DENSE, SPARSE = "dense", "sparse"


class LayerKind(NamedTuple):
    """What one layer is made of: its attention (``full_attention`` |
    ``sliding_attention`` | ``latent_attention`` | ``linear_attention`` |
    ``eva_attention`` | ``full_attention_nope``), its query heads, its FFN
    (``dense`` | ``sparse``).  Window, rotary recipe and widths follow from
    these and the configuration."""
    attn: str
    heads: int
    ffn: str


class Layout(NamedTuple):
    """The layers as a leading group, a period repeated ``n_periods`` times
    and a trailing part of one more period: ``lead + period * n_periods +
    period[:n_trail]``.  Every layer alike is one slot and no lead.
    ``runs`` (``_alike_runs``) cuts the period's slots into ``((first slot,
    count), ...)``: a run of several consecutive slots alike is held stacked
    ``[n_periods, count, ...]`` and traced as one body (an inner scan), every
    other slot is a run of one, stacked ``[n_periods, ...]``."""
    lead: Tuple[LayerKind, ...]
    period: Tuple[LayerKind, ...]
    n_periods: int
    n_trail: int

    @property
    def uniform(self) -> bool:
        return not self.lead and len(self.period) == 1

    @property
    def runs(self) -> Tuple[Tuple[int, int], ...]:
        return _alike_runs(self.period)

    @property
    def kinds(self) -> Tuple[LayerKind, ...]:
        return (self.lead + self.period * self.n_periods
                + self.period[:self.n_trail])

    def run_layers(self, s: int, count: int) -> np.ndarray:
        """Where in ``kinds`` the layers of the run ``(s, count)`` stand:
        [n_periods], or for a run of several [n_periods, count], as its
        leaves are stacked."""
        at = (len(self.lead) + len(self.period)
              * np.arange(self.n_periods)[:, None] + s + np.arange(count))
        return at if count > 1 else at[:, 0]


def _layout(kinds: Tuple[LayerKind, ...], period: int = 0) -> Layout:
    """The shortest ``lead + period`` that writes ``kinds`` (of several as
    short, the shortest lead), or with ``period`` given the shortest lead
    before layers of that period."""
    L = len(kinds)

    def periodic(rest, p):
        return all(k == rest[i % p] for i, k in enumerate(rest))

    found = [(n_lead + p, n_lead, p)
             for n_lead in range(L)
             for p in ([period] if period else range(1, L - n_lead + 1))
             if p <= L - n_lead and periodic(kinds[n_lead:], p)]
    if not found:
        raise ValueError(f"the layers {kinds} have no period {period}")
    _, n_lead, p = min(found)
    return Layout(kinds[:n_lead], kinds[n_lead:n_lead + p],
                  (L - n_lead) // p, (L - n_lead) % p)


# Consecutive slots alike are one stacked run from this many on.  Shorter runs
# stay slots of their own, which is the tree a period of three sliding layers
# and a full one always was: saved checkpoints hold it, and the benchmark's
# accepted readers index it a slot at a time
# (``benchmarks/runners/lm_train_kinds.py:_leaf``, ``benchmarks/reference/
# laguna_lm.py:layer``: files only a ``benchmark`` PR may edit).
STACKED_RUN = 4


@functools.lru_cache(maxsize=None)
def _alike_runs(period: Tuple[LayerKind, ...]):
    """The period's slots as ``((first slot, count), ...)``: ``STACKED_RUN``
    or more consecutive slots alike are one run, every other slot a run of
    one."""
    runs, s = [], 0
    while s < len(period):
        n = 1
        while s + n < len(period) and period[s + n] == period[s]:
            n += 1
        runs += [(s, n)] if n >= STACKED_RUN else [(s + j, 1)
                                                   for j in range(n)]
        s += n
    return tuple(runs)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 512
    n_layers: int = 4
    n_heads: int = 8
    hidden: int = 1408          # SwiGLU inner dim
    max_seq: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    # Mixture-of-Experts: 0 experts = dense SwiGLU MLP; >0 replaces every
    # MLP with a top_k-routed expert layer (models/moe.py), experts
    # sharded over the mesh's ``ep`` axis.
    num_experts: int = 0
    top_k: int = 2
    aux_loss_coef: float = 0.01
    # Router z-loss (mean logsumexp(router logits)^2 a layer): OLMoE 0.001.
    router_z_loss_coef: float = 0.0
    # Renormalise a token's top_k router weights to sum to 1 (OLMoE: no).
    norm_topk_prob: bool = True
    # models/moe.py's schedules: "grouped" = dropless sort + grouped matmul,
    # FLOPs of exactly the routes (what the OLMoE cell runs; no ``ep``
    # axis); "dense" = exact all-experts dispatch (the tests' oracle, and
    # the one GSPMD partitions over ``ep``).
    moe_dispatch: str = "dense"
    # QK-norm (OLMoE): an RMSNorm with its own gain over the whole
    # dim-wide q and k projections, before the split into heads and rotary.
    qk_norm: bool = False
    # remat: gradient checkpointing — recompute each layer's forward during
    # the backward pass instead of saving activations.  Trades ~1/3 more
    # matmul FLOPs for O(layers·B·T·dim) activation memory, the knob that
    # lets batch·seq scale to MXU-bound sizes on one chip.  (A save-the-
    # attention-output policy was tried and REMOVED: saving the output
    # prunes no backward recompute — grads w.r.t. wq/wk/wv still need the
    # attention internals — so it only added residual memory.)
    remat: bool = False
    # remat_policy (with remat=True):
    # - "full": save only layer boundaries; the backward re-runs the whole
    #   layer forward (~2P extra matmul FLOPs — bills MFU at ~6/8 of the
    #   hardware's actual utilization).  Minimal memory.
    # - "dots": jax.checkpoint_policies selective remat — save every
    #   matmul output (q/k/v/wo/w1/w3/w2 projections), recompute only the
    #   cheap tensor ops (norms, rope) and the flash-attention kernel
    #   (its custom_vjp output is not a dot, so it replays from the saved
    #   q/k/v).  Recompute tax drops from ~2P to roughly the attention
    #   FLOPs; memory grows to O(layers·B·T·(5·dim+2·hidden)).
    remat_policy: str = "full"
    # scan_layers: stack the per-layer params into [L, ...] arrays and run
    # ``lax.scan`` over them — O(1) trace/compile time in depth and the
    # natural pairing with remat (XLA sees one layer body once).
    scan_layers: bool = False
    # Pipeline parallelism: with a ``pp`` mesh axis and M > 0, the layer
    # stack splits into pp stages and batches flow through the GPipe
    # microbatch schedule (``parallel/pipeline.py``).  Requires
    # scan_layers (stages slice the stacked params), dense MLPs, sp == 1,
    # and batch divisible by M.
    pipeline_microbatches: int = 0
    # ---- Layers of different kinds.  The defaults are every layer alike:
    # multi-head attention of ``dim // n_heads`` wide heads, one rotary
    # base, no window, no gate.
    # head_dim: 0 = ``dim // n_heads``; else ``wq``/``wo`` are ``dim x
    # heads*head_dim`` whatever ``dim`` is.
    head_dim: int = 0
    # n_kv_heads: 0 = as many as query heads; else grouped K/V heads, query
    # head j reading K/V head ``j // (heads // n_kv_heads)``.
    n_kv_heads: int = 0
    # Per layer (tuples of n_layers, or None = all alike): the attention
    # kind, the query heads (None = n_heads) and the FFN kind (None =
    # ``sparse`` everywhere if num_experts else ``dense``).  The layers are
    # held as ``layout`` writes them: a leading group, then a period whose
    # slots are stacked over its repetitions and scanned.
    layer_types: Optional[Tuple[str, ...]] = None
    heads_per_layer: Optional[Tuple[int, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    # The pattern's period where the layers cannot say it themselves (a
    # model cut to its leading layer and one period); 0 = the shortest.
    layer_period: int = 0
    # sliding_attention layers see the keys ``t - sliding_window < s <= t``.
    sliding_window: int = 0
    # Rotary recipe by attention kind (a ``Rope``, or its fields as a dict);
    # None = ``Rope(theta=rope_theta)``.
    rope_full: Optional[Rope] = None
    rope_sliding: Optional[Rope] = None
    # "per_head": the attention output of every query head is multiplied by
    # sigmoid(h wg), one scalar a head and position, before ``wo`` (on
    # latent and linear layers too).
    attn_gate: str = ""
    # Width of the ``dense`` layers' SwiGLU where it differs from the
    # experts' ``hidden`` (0 = ``hidden``).
    dense_hidden: int = 0
    # A share of the experts (models/moe.py): the router keeps
    # ``num_experts`` columns, the layer holds ``experts_held`` of them from
    # ``experts_first`` on (0 = all).  ``routed_scale`` multiplies the top-k
    # weights; ``shared_expert_hidden`` > 0 adds the always-on SwiGLU.
    experts_held: int = 0
    experts_first: int = 0
    routed_scale: float = 1.0
    shared_expert_hidden: int = 0
    # The router's scores (models/moe.py:_routing): "softmax", or "sigmoid"
    # with a per-expert correction bias (leaf ``router_bias``) that enters
    # the top-k choice and not the weights and that the train step moves by
    # rule, ``b_e += router_bias_rate * sign(mean(load) - load_e)`` over the
    # step's own counted routes, and no gradient does.
    router_scoring: str = "softmax"
    router_bias_rate: float = 0.001
    # The experts in ``n_group`` groups of adjacent ones, a token choosing
    # among the experts of its ``topk_group`` best groups only
    # (models/moe.py:_kept_groups); 1 / 1 = no limit.  With a share held the
    # step then hands back, beside its counted routes, the tokens that kept
    # the group of the first expert held here (``TransformerTrainer.kept``).
    n_group: int = 1
    topk_group: int = 1
    # Where a routed layer's router reads: "mlp" = the rows its experts
    # multiply (the FFN sub-layer's normed input); "attn" = the attention
    # sub-layer's normed input, so that the choice is made before attention
    # runs: the block routes under the scope ``route_early`` and the FFN
    # sorts and weighs by the route it is handed (models/moe.py).
    router_input: str = "mlp"
    # What squashes the ``w1`` branch of every gated FFN (dense, shared,
    # routed): "silu" = SwiGLU, "relu" = ReGLU (common.GATE_ACTS).
    ffn_act: str = "silu"
    # ---- ``latent_attention`` layers: the latents' and the heads' widths
    # (``q_lora_rank`` 0 = no query latent), YaRN's mscale, the rotated
    # parts' recipe.  ``attention/latent.py`` has the equations.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    attn_mscale: float = 1.0
    rope_latent: Optional[Rope] = None
    # ---- ``linear_attention`` layers: the causal convolution's taps and the
    # decay's lower bound.  ``attention/linear.py`` has the equations.
    linear_conv_kernel: int = 4
    kda_lower_bound: float = -5.0
    # ---- ``eva_attention`` layers: a query's own window and the positions
    # a summary pools.  ``attention/eva.py`` has the equations.
    eva_window: int = 0
    eva_chunk: int = 0
    # ---- Hyper-connections (arXiv:2512.24880 over arXiv:2409.19606): 0 =
    # the residual ``x + f(x)``; n > 0 = n residual streams ``[B, T, dim]``
    # (a tuple: the scan's carry), every sub-layer reading ``u = sum_i
    # H_pre[i] X[i]`` and writing
    # ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] f(u)`` with ``H_pre``,
    # ``H_post`` sigmoid gates and ``H_res`` made doubly stochastic by
    # ``hc_sinkhorn_iters`` Sinkhorn-Knopp iterations of ``exp(clip(.,
    # hc_res_clamp_min, hc_res_clamp_max))``, all three linear in the
    # token's RMS-normed streams (``_hc_gates``).
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # ---- Multi-token prediction (arXiv:2412.19437, section 2.2): 0 = none;
    # 1 = one module: ``[norm(x_t) | norm(E[tok_{t+1}])] proj`` through one
    # layer of its own (the last layer's kind), its own final norm, the
    # shared embedding and head; ``loss += mtp_loss_coef * CE(token t+2)``.
    mtp_layers: int = 0
    mtp_loss_coef: float = 0.3
    # ---- The residual stream's dtype where it is not the compute dtype
    # (None): "float32" keeps ``x + f(norm(x))`` summed in float32 while the
    # norms and sub-layers compute in ``compute_dtype``.
    residual_dtype: Any = None
    # RMSNorm gains stored as offsets from one: ``x / rms(x) * (1 + w)``, the
    # leaves drawn as zeros.
    norm_unit_offset: bool = False
    # n > 1: the head holds ``n * vocab_size`` columns, head i predicting
    # token t + 1 + i from position t's state; the loss is the mean over the
    # heads of each one's mean cross-entropy over the positions that have its
    # target.
    n_pred_heads: int = 1
    # The logits' dtype where it is not the compute dtype (None): "float32"
    # has the head's product accumulate and leave in float32.
    logits_dtype: Any = None
    # > 0: every matrix (embedding and head too) is drawn N(0, init_std^2);
    # 0 = fan-in scaling, the embedding 0.02.
    init_std: float = 0.0

    def __post_init__(self):
        def put(name, value):
            object.__setattr__(self, name, value)

        if not self.head_dim:
            put("head_dim", self.dim // self.n_heads)
        for name in ("layer_types", "heads_per_layer", "mlp_layer_types"):
            value = getattr(self, name)
            if value is not None:
                if len(value) != self.n_layers:
                    raise ValueError(f"{name} lists {len(value)} layers, "
                                     f"n_layers is {self.n_layers}")
                put(name, tuple(value))
        for name in ("rope_full", "rope_sliding", "rope_latent"):
            if isinstance(getattr(self, name), dict):
                put(name, Rope(**getattr(self, name)))
        for name in ("residual_dtype", "logits_dtype"):
            if getattr(self, name) is not None:
                put(name, jnp.dtype(getattr(self, name)))
        if self.n_pred_heads < 1 or (self.n_pred_heads > 1
                                     and self.mtp_layers):
            raise ValueError(
                f"n_pred_heads={self.n_pred_heads}: at least one head, and "
                "parallel heads or a prediction module (mtp_layers), not "
                "both")
        for k in self.layout.kinds:
            if k.attn not in KINDS or k.ffn not in (DENSE, SPARSE):
                raise ValueError(f"unknown layer kind {k}")
            if k.ffn == SPARSE and not self.num_experts:
                raise ValueError("sparse layers need num_experts > 0")
            KINDS[k.attn].check(self, k)
        if self.attn_gate not in ("", "per_head"):
            raise ValueError(f"unknown attn_gate '{self.attn_gate}'")
        if self.router_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown router_scoring '{self.router_scoring}'")
        if self.rule_bias and (self.aux_loss_coef
                               or self.router_z_loss_coef):
            raise ValueError(
                "router_scoring='sigmoid' balances by its bias rule: set "
                "aux_loss_coef and router_z_loss_coef to 0 (they are "
                "softmax routing's)")
        if self.n_group < 1 or not 0 < self.topk_group <= self.n_group or (
                self.n_group > 1 and (self.num_experts % self.n_group
                                      or self.num_experts < 2 * self.n_group)):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                f"{self.num_experts} experts must lie in n_group groups of "
                "at least two, of which 1..n_group are kept")
        if self.router_input not in ("mlp", "attn") or (
                self.router_input == "attn"
                and (self.hc_mult or not self.num_experts)):
            raise ValueError(
                f"router_input='{self.router_input}': 'mlp' or, with routed "
                "layers and one residual stream (no hc_mult), 'attn'")
        if self.ffn_act not in GATE_ACTS:
            raise ValueError(f"unknown ffn_act '{self.ffn_act}' "
                             f"(expected {'|'.join(GATE_ACTS)})")
        if self.hc_mult < 0 or self.hc_mult == 1:
            raise ValueError(f"hc_mult={self.hc_mult}: 0 (one stream, no "
                             "hyper-connections) or at least 2 streams")
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                f"mtp_layers={self.mtp_layers}: one prediction depth beyond "
                "the next token is what there is (0 or 1)")

    @functools.cached_property
    def layout(self) -> Layout:
        # None = every layer ``KINDS``' first kind, full attention
        plain, ffn = next(iter(KINDS)), SPARSE if self.num_experts else DENSE
        return _layout(tuple(
            LayerKind((self.layer_types or (plain,) * self.n_layers)[i],
                      (self.heads_per_layer
                       or (self.n_heads,) * self.n_layers)[i],
                      (self.mlp_layer_types or (ffn,) * self.n_layers)[i])
            for i in range(self.n_layers)), self.layer_period)

    @property
    def held(self) -> Optional[Tuple[int, int]]:
        """``(first, count)`` of the experts a layer holds, or None = all."""
        if not self.experts_held:
            return None
        return (self.experts_first, self.experts_held)

    @property
    def counts_routes(self) -> bool:
        """Whether the train step hands back each routed layer's counted
        routes: where only a share of the experts is held, how many routes
        reached it is something no trace knows."""
        return bool(self.experts_held) and self.experts_held < self.num_experts

    @property
    def rule_bias(self) -> bool:
        """Whether the routed layers hold a ``router_bias`` that the train
        step moves by rule."""
        return bool(self.num_experts) and self.router_scoring == "sigmoid"

    def rope(self, attn: str) -> Rope:
        return KINDS[attn].rope(self) or Rope(theta=self.rope_theta)


def _hc_init(cfg: TransformerConfig, w: Draw, sub: str):
    """Sub-layer ``sub``'s hyper-connection leaves: ``phi [n*dim, 2n + n*n]``
    (columns: pre, post, res row-major), ``alpha [3]``, ``b [2n + n*n]``.
    With ``alpha`` 0 these are ``H_pre = 1/n``, ``H_post = 1``, ``H_res``
    the identity to 1e-3: the one-stream pre-norm model on n equal
    streams."""
    n = cfg.hc_mult
    return {"phi": w(f"{sub}.phi", n * cfg.dim, 2 * n + n * n,
                     scale=0.02 * (n * cfg.dim) ** -0.5),
            "alpha": np.full(3, 0.01, np.float32),
            "b": np.concatenate([np.full(n, math.log(1 / (n - 1))),
                                 np.zeros(n),
                                 8.0 * np.eye(n).ravel()]).astype(np.float32)}


def _init_layer(cfg: TransformerConfig, kind: LayerKind, w: Draw):
    """One layer's leaves from its ``Draw``: a leaf's values follow from the
    seed, the layer's index and the leaf's name, so a leaf added here or in a
    kind's ``init`` moves no other.  (A routed cell's rates follow the drawn
    router.)"""
    lyr = KINDS[kind.attn].init(cfg, kind, w)
    lyr.update(attn_norm=unit_gain(cfg, cfg.dim),
               mlp_norm=unit_gain(cfg, cfg.dim))
    if cfg.qk_norm:
        kv = cfg.n_kv_heads or kind.heads
        lyr.update(q_norm=unit_gain(cfg, kind.heads * cfg.head_dim),
                   k_norm=unit_gain(cfg, kv * cfg.head_dim))
    if kind.ffn == SPARSE:
        # router, w1, w3, w2 at the layer's top level, expert-indexed
        lyr.update(moe_leaves(w, cfg.dim, cfg.hidden, cfg.num_experts,
                              cfg.experts_held, cfg.router_scoring))
    else:
        hidden = cfg.dense_hidden or cfg.hidden
        lyr.update({
            "w1": w("w1", cfg.dim, hidden),   # gate
            "w3": w("w3", cfg.dim, hidden),   # up
            "w2": w("w2", hidden, cfg.dim),   # down
        })
    if cfg.attn_gate:
        lyr["wg"] = w("wg", cfg.dim, kind.heads)
    if kind.ffn == SPARSE and cfg.shared_expert_hidden:
        shared = cfg.shared_expert_hidden
        lyr.update(shared_w1=w("shared_w1", cfg.dim, shared),
                   shared_w3=w("shared_w3", cfg.dim, shared),
                   shared_w2=w("shared_w2", shared, cfg.dim))
    if cfg.hc_mult:
        lyr.update(hc_attn=_hc_init(cfg, w, "hc_attn"),
                   hc_mlp=_hc_init(cfg, w, "hc_mlp"))
    return lyr


def _draw_tree(cfg: TransformerConfig, root) -> Dict[str, Any]:
    """``init_params``' tree from the seed's key ``root``, traced: one
    program draws every leaf in its final shape."""
    lay = cfg.layout

    def layers_at(kind, index):
        """The leaves of the layer of ``kind`` at ``index`` of ``lay.kinds``;
        with an array of indices, of those layers, stacked as the array is
        shaped: a loop of the one-layer draw that writes each layer where it
        rests, so no stack of whole leaves runs, and one body to compile
        however many layers there are."""
        draw = lambda i: _init_layer(
            cfg, kind, Draw(jax.random.fold_in(root, i), cfg.init_std))
        for _ in range(np.ndim(index)):
            draw = functools.partial(jax.lax.map, draw)
        return draw(jnp.asarray(index, jnp.uint32))

    if not cfg.scan_layers:
        # alike layers by one loop, one body to compile, then taken apart
        where = {kind: [i for i, k in enumerate(lay.kinds) if k == kind]
                 for kind in set(lay.kinds)}
        drawn = {kind: layers_at(kind, np.asarray(at))
                 for kind, at in where.items()}
        layers = [jax.tree_util.tree_map(
            lambda leaf: leaf[where[kind].index(i)], drawn[kind])
            for i, kind in enumerate(lay.kinds)]
    elif lay.uniform:
        layers = layers_at(lay.period[0], np.arange(cfg.n_layers))
    else:
        n_lead = len(lay.lead)
        n_body = n_lead + len(lay.period) * lay.n_periods
        layers = {"lead": [layers_at(kind, i)
                           for i, kind in enumerate(lay.lead)],
                  "period": [layers_at(lay.period[s], lay.run_layers(s, count))
                             for s, count in lay.runs],
                  "trail": [layers_at(kind, n_body + i) for i, kind
                            in enumerate(lay.period[:lay.n_trail])]}
    w = Draw(root, cfg.init_std)
    params = {
        "embed": w("embed", cfg.vocab_size, cfg.dim, scale=0.02),
        "out_norm": unit_gain(cfg, cfg.dim),
        "head": w("head", cfg.dim, cfg.n_pred_heads * cfg.vocab_size),
        "layers": layers,
    }
    if cfg.mtp_layers:               # its layer: one past the model's last
        params["mtp"] = {
            "proj": w("mtp.proj", 2 * cfg.dim, cfg.dim),
            "h_norm": unit_gain(cfg, cfg.dim),
            "e_norm": unit_gain(cfg, cfg.dim),
            "out_norm": unit_gain(cfg, cfg.dim),
            "layer": layers_at(lay.kinds[-1], cfg.n_layers)}
    return params


@functools.lru_cache(maxsize=64)
def _draw_program(cfg: TransformerConfig, placed, tree):
    """``_draw_tree`` jitted, its leaves drawn to the shardings ``placed``
    (the leaves of ``tree``) or, with no ``tree``, on the default device.
    Kept a configuration and placement, so a second draw compiles nothing."""
    return jax.jit(
        functools.partial(_draw_tree, cfg),
        out_shardings=None if tree is None else tree.unflatten(placed))


def init_params(cfg: TransformerConfig, seed: int = 0,
                shardings=None) -> Dict[str, Any]:
    """Float32 master weights, normal draws (``common.Draw``; the gains ones,
    the rest as the kinds say), made on the device from ``jax.random.key(
    seed)``: nothing the size of a matrix is made on, or crosses, the host.
    Every leaf comes to be in its final shape, on ``shardings``' sharding
    (``param_shardings``' tree; None = the default device), a function of
    (seed, layer index, leaf name) alone.  ``layers`` is a list of per-layer
    dicts, or under ``scan_layers`` the layers as ``group_layers`` holds
    them, the same values either way."""
    placed, tree = ((), None) if shardings is None else (
        jax.tree_util.tree_flatten(shardings))
    return _draw_program(cfg, tuple(placed), tree)(jax.random.key(seed))


def stack_layer_params(layers):
    """List of per-layer param dicts → one dict of stacked [L, ...] arrays.

    The scan-format params: leaf k holds ``stack([lyr[k] for lyr in
    layers])``.  Works on numpy or jax leaves (nested dicts included);
    for tests converting loop-format params for parity checks
    (``init_params`` draws a stacked slot stacked).
    """
    return jax.tree_util.tree_map(
        lambda *xs: (np.stack(xs) if isinstance(xs[0], np.ndarray)
                     else jnp.stack(xs)), *layers)


def group_layers(cfg: TransformerConfig, layers):
    """A list of per-layer dicts as the scan holds them.  Every layer alike
    (``layout.uniform``): one dict of leaves stacked ``[L, ...]``
    (``stack_layer_params``).  Else ``{"lead": [layer, ...], "period":
    [slot, ...], "trail": [layer, ...]}``, slot ``s`` holding the s-th layer
    of every period stacked ``[n_periods, ...]``; a run of several slots
    alike (``Layout.runs``) is one entry, stacked ``[n_periods, count,
    ...]``."""
    lay = cfg.layout
    if lay.uniform:
        return stack_layer_params(layers)
    n_lead, p = len(lay.lead), len(lay.period)
    body = layers[n_lead:n_lead + p * lay.n_periods]

    def stacked(s, count):
        if count == 1:
            return stack_layer_params(body[s::p])
        return stack_layer_params(
            [stack_layer_params(body[at + s:at + s + count])
             for at in range(0, len(body), p)])

    return {"lead": list(layers[:n_lead]),
            "period": [stacked(s, count) for s, count in lay.runs],
            "trail": list(layers[n_lead + p * lay.n_periods:])}


def _grouped(cfg: TransformerConfig, layers):
    """``(lead, period, trail)`` of scan-format ``layers``: lists of layer
    dicts around a tuple of stacked slots (one slot where every layer is
    alike)."""
    if cfg.layout.uniform:
        return [], (layers,), []
    return layers["lead"], tuple(layers["period"]), layers["trail"]


def _layer_pspecs(cfg: TransformerConfig, mesh: Mesh,
                  kind: Optional[LayerKind] = None) -> Dict[str, Any]:
    """Per-layer weight PartitionSpecs for the Megatron-style tp layout:
    attention io dims and MLP hidden shard over ``tp`` (column-parallel
    wq/wk/wv/w1/w3 and the per-head gate, row-parallel wo/w2); norms
    replicated.  The QK-norm
    gains are replicated too: its mean of squares runs over the whole
    tp-sharded projection, which GSPMD completes with an all-reduce (the
    manual tp layout inside a pipeline stage refuses QK-norm by name)."""
    kind = kind or cfg.layout.period[0]
    tp = "tp" if "tp" in mesh.shape else None
    if tp and (cfg.n_kv_heads or kind.heads) % mesh.shape["tp"]:
        raise ValueError(
            f"{cfg.n_kv_heads or kind.heads} K/V heads do not divide over "
            f"the 'tp' axis ({mesh.shape['tp']}): wk/wv shard by head")

    attn = KINDS[kind.attn]
    layer = attn.pspecs(cfg, kind, tp, mesh.shape["tp"] if tp else 1)
    layer.update(attn_norm=P(None), mlp_norm=P(None))
    if cfg.qk_norm:
        layer.update(q_norm=P(None), k_norm=P(None))
    if cfg.attn_gate:
        layer["wg"] = P(None, tp if attn.gate_tp else None)
    if kind.ffn == SPARSE:
        layer.update(moe_pspecs(mesh))
        if cfg.rule_bias:
            layer["router_bias"] = P(None)
        if cfg.shared_expert_hidden:
            layer.update(shared_w1=P(None, tp), shared_w3=P(None, tp),
                         shared_w2=P(tp, None))
    else:
        layer.update({"w1": P(None, tp), "w3": P(None, tp),
                      "w2": P(tp, None)})
    if cfg.hc_mult:              # small, float32, replicated
        for sub in ("hc_attn", "hc_mlp"):
            layer[sub] = {"phi": P(None, None), "alpha": P(None),
                          "b": P(None)}
    return layer


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Dict[str, Any]:
    """TP layout: attention io dims, MLP hidden, and vocab shard over ``tp``;
    everything else replicated (dp/sp shard activations, not weights).

    Scan-format params get the same per-layer specs with an unsharded
    leading layer dim."""
    tp = "tp" if "tp" in mesh.shape else None
    lay = cfg.layout
    is_spec = lambda x: isinstance(x, P)

    def sharded(kind, *lead):
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, P(*lead, *spec)),
            _layer_pspecs(cfg, mesh, kind), is_leaf=is_spec)

    if cfg.scan_layers:
        # With pipeline parallelism the stacked layer dim shards over
        # ``pp`` (each stage holds its own layers); otherwise replicated.
        lead = ("pp" if ("pp" in mesh.shape and cfg.pipeline_microbatches
                         and cfg.n_layers % mesh.shape["pp"] == 0)
                else None)
        if lay.uniform:
            layers = sharded(lay.period[0], lead)
        else:
            layers = {"lead": [sharded(k) for k in lay.lead],
                      "period": [sharded(lay.period[s],
                                         *(None,) * (1 + (count > 1)))
                                 for s, count in lay.runs],
                      "trail": [sharded(k)
                                for k in lay.period[:lay.n_trail]]}
    else:
        layers = [sharded(kind) for kind in lay.kinds]

    def s(*spec):
        return NamedSharding(mesh, P(*spec))

    out = {
        "embed": s(None, None),
        "out_norm": s(None),
        "head": s(None, tp),
        "layers": layers,
    }
    if cfg.mtp_layers:
        out["mtp"] = {"proj": s(None, None), "h_norm": s(None),
                      "e_norm": s(None), "out_norm": s(None),
                      "layer": sharded(lay.kinds[-1])}
    return out


# ---- hyper-connections.  The streams are a tuple of n ``[B, T, dim]``
# arrays (not one ``[B, T, n, dim]``: no tile is padded from n rows, and the
# n outputs of a mix are siblings that read each input stream once), the
# gates ``[n, B, T]`` and ``[n, n, B, T]`` float32, planes of tokens, so
# that every Sinkhorn step is elementwise over whole planes.
def _hc_gates(X, hc, cfg: TransformerConfig):
    """``(H_pre [n, B, T], H_post [n, B, T], H_res [n, n, B, T])``, float32,
    for the streams ``X`` from one sub-layer's leaves ``hc``.  The RMS norm
    over a token's ``n * dim`` values is a scalar a token, so it is applied
    to the ``2n + n*n`` products and not to the streams (the same number,
    without a normed copy of X).  The matmuls are dots without batch dims:
    remat policy "dots" saves their ``[B, T, 2n + n*n]`` outputs, and gates
    and Sinkhorn are recomputed from them."""
    n, f32 = cfg.hc_mult, jnp.float32
    metrics.counter("hc.traced", {"n": str(n)}).inc()
    with jax.named_scope("hc.gates"):
        dim = X[0].shape[-1]
        phi = hc["phi"].astype(X[0].dtype).reshape(n, dim, -1)
        raw = sum(jnp.dot(x, phi[i], preferred_element_type=f32)
                  for i, x in enumerate(X))                  # [B,T,2n+n*n]
        var = sum(jnp.mean(jnp.square(x.astype(f32)), axis=-1)
                  for x in X) / n                            # [B,T]
        a = jnp.moveaxis(raw, -1, 0) * jax.lax.rsqrt(var + cfg.norm_eps)
        alpha = hc["alpha"].astype(f32)
        b = hc["b"].astype(f32)[:, None, None]
        pre = jax.nn.sigmoid(alpha[0] * a[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + b[n:2 * n])
        res = jnp.exp(jnp.clip(alpha[2] * a[2 * n:] + b[2 * n:],
                               cfg.hc_res_clamp_min, cfg.hc_res_clamp_max)
                      ).reshape(n, n, *var.shape)
        for _ in range(cfg.hc_sinkhorn_iters):
            res = res / (jnp.sum(res, axis=1, keepdims=True) + cfg.hc_eps)
            res = res / (jnp.sum(res, axis=0, keepdims=True) + cfg.hc_eps)
    return pre, post, res


def _hc_read(X, pre):
    """The sub-layer's input ``sum_i H_pre[i] X[i]`` [B, T, dim]."""
    with jax.named_scope("hc.mix"):
        u = sum(pre[i][..., None] * x.astype(jnp.float32)
                for i, x in enumerate(X))
        return u.astype(X[0].dtype)


def _hc_write(X, post, res, y):
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``, y [B, T, dim]."""
    with jax.named_scope("hc.mix"):
        Xf, yf = [x.astype(jnp.float32) for x in X], y.astype(jnp.float32)
        return tuple(
            (sum(res[i, j][..., None] * xj for j, xj in enumerate(Xf))
             + post[i][..., None] * yf).astype(y.dtype)
            for i in range(len(X)))


def _hc_mean(X):
    """The streams' mean: what goes on to a final norm."""
    return (sum(x.astype(jnp.float32) for x in X) / len(X)).astype(X[0].dtype)


def transformer_forward(params, tokens, cfg: TransformerConfig,
                        mesh: Optional[Mesh] = None,
                        return_aux: bool = False):
    """tokens [B, T] int32 → logits [B, T, vocab] (compute dtype, or
    ``logits_dtype``; with ``n_pred_heads`` n > 1 ``[B, T, n * vocab]``, head
    i's columns ``[i * vocab, (i + 1) * vocab)`` predicting token t + 1 + i).

    With ``return_aux=True`` also returns the MoE auxiliary loss summed
    over the layers, weighted as ``lm_loss`` adds it: ``aux_loss_coef`` x
    the load-balancing term + ``router_z_loss_coef`` x the router z-loss
    (zero for dense configs)."""
    logits, aux, *_ = _forward(params, tokens, cfg, mesh)
    return (logits, aux) if return_aux else logits


def expert_load(params, tokens, cfg: TransformerConfig,
                mesh: Optional[Mesh] = None):
    """Routes each expert of each routed layer is sent for ``tokens``
    [B, T]: int32 ``[routed layers, num_experts]``, every row summing to
    ``B*T*top_k``.  Where the layers hold a share of the experts
    (``experts_held``) a row is ``[experts_held + 1]``: the held experts'
    routes and, last, the routes that went elsewhere, which is what the
    train step counts too (``TransformerTrainer.routes``).
    A diagnostic off the step (one forward pass): how uneven the groups of
    the grouped schedule are."""
    if not cfg.num_experts:
        raise ValueError("expert_load: the configuration has no experts")
    return jax.jit(lambda p, t: _routes(cfg, _forward(p, t, cfg, mesh)[2]))(
        params, jnp.asarray(tokens, jnp.int32))


def _routes(cfg: TransformerConfig, load):
    """The rows ``expert_load`` and the train step hand back, from the
    layers' ``load``: itself, but where a bias rule had every expert's
    routes counted (``[layers, E]``) and a share is held, the share's rows
    ``[layers, experts_held + 1]``."""
    if load is None or not (cfg.rule_bias and cfg.counts_routes):
        return load
    first, count = cfg.held
    mine = load[:, first:first + count]
    return jnp.concatenate(
        [mine, jnp.sum(load, axis=1, keepdims=True)
         - jnp.sum(mine, axis=1, keepdims=True)], axis=1)


def _layer_order(parts, counts):
    """What the period's scan stacked of its routed layers, ``[n_periods,
    ...]`` a slot and ``[n_periods, count, ...]`` a run of ``count``
    (``counts``, a part each), as ``[layers, ...]`` in layer order."""
    if len(parts) == 1 and counts[0] == 1:
        return parts[0]
    slots = jnp.concatenate(
        [part if count > 1 else jnp.expand_dims(part, 1)
         for part, count in zip(parts, counts)], axis=1)
    return slots.reshape(-1, *slots.shape[2:])


def _forward(params, tokens, cfg: TransformerConfig, mesh: Optional[Mesh]):
    """``(logits, weighted auxiliary loss, expert load [routed layers, E]
    or None, the prediction module's logits or None, kept)``.  With
    ``mtp_layers`` the module's layer is the load's last row.  ``kept``: where
    groups limit the router's choice (``n_group`` > 1), the tokens of each
    routed layer that kept the group of the first expert held (int32
    ``[routed layers]``), else None."""
    lay = cfg.layout
    if tokens.shape[1] > cfg.max_seq:
        raise ValueError(
            f"sequence length {tokens.shape[1]} exceeds max_seq "
            f"{cfg.max_seq}")
    use_pp = (mesh is not None and cfg.pipeline_microbatches > 0
              and int(mesh.shape.get("pp", 1)) > 1)
    # GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    # automatically partitioned"), so on ANY multi-device mesh attention
    # runs inside ring_attention's shard_map — sp == 1 is its no-ring
    # degenerate case.  Pipeline stages already sit inside gpipe's
    # shard_map and call the local kernel directly.
    ctx = Ctx(cfg, mesh,
              ring=mesh is not None and mesh.size > 1 and not use_pp)
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(ctx.res_dt)    # [B,T,dim]
    _refuse(ctx, use_pp)
    blocks = {kind: _make_block(ctx, kind) for kind in set(lay.kinds)}
    mtp_logits = None
    if use_pp:
        x = _run_pipeline(ctx, blocks, params["layers"], x)
        aux_total, counted = jnp.float32(0), None
    else:
        # ``counted``: the routed layers' ``(load, kept)`` in layer order.
        x, aux_total, counted = _run_stack(ctx, blocks, params["layers"], x)
        if cfg.mtp_layers:
            mtp_logits, aux_total = _mtp(ctx, blocks[lay.kinds[-1]], params,
                                         tokens, x, aux_total, counted)
        counted = (None if not counted else counted[0] if len(counted) == 1
                   else jax.tree_util.tree_map(
                       lambda *parts: jnp.concatenate(parts), *counted))
    load, kept = counted or (None, None)
    with jax.named_scope("head"):
        x = rms_norm(ctx.read(x), ctx.gain(params["out_norm"]), cfg.norm_eps)
        if cfg.logits_dtype is not None:
            logits = jnp.dot(x, params["head"].astype(ctx.dt),
                             preferred_element_type=cfg.logits_dtype)
        else:
            logits = x @ params["head"].astype(ctx.dt)
    return logits, aux_total, load, mtp_logits, kept


def _refuse(ctx: Ctx, use_pp: bool) -> None:
    """Raise on a mesh or a composition this model does not run: the routed
    schedule's, each attention kind's own (``AttnKind.refuse``, in
    ``KINDS``' order), the residual dtype's and the pipeline's."""
    cfg, mesh = ctx.cfg, ctx.mesh
    if (cfg.num_experts and cfg.moe_dispatch == "grouped"
            and mesh is not None and int(mesh.shape.get("ep", 1)) > 1):
        raise ValueError(
            "moe_dispatch='grouped' does not run over an 'ep' mesh axis "
            f"(ep={mesh.shape['ep']}): its grouped matmul wants every "
            "expert's weights on the chip that holds the rows; use "
            "moe_dispatch='dense', which GSPMD partitions over 'ep'")
    present = {k.attn for k in cfg.layout.kinds}
    for name, attn in KINDS.items():
        if name in present:
            attn.refuse(cfg, mesh)
    if ctx.wide and (cfg.hc_mult or use_pp or cfg.mtp_layers):
        raise ValueError(
            "residual_dtype does not compose with hc_mult, mtp_layers or "
            "pipeline_microbatches: the streams' mixes, the module's "
            "projection and the stages pass the compute dtype")
    if use_pp and (cfg.hc_mult or cfg.mtp_layers):
        raise ValueError(
            "pipeline_microbatches does not compose with hc_mult or "
            "mtp_layers: stages pass one [B, T, dim] stream and the "
            "prediction module reads the last stage's hidden state")


def _attn_scopes(ctx: Ctx, kind: LayerKind):
    """``attn`` and, inside it where the layers differ, the kind's own."""
    both = contextlib.ExitStack()
    both.enter_context(jax.named_scope("attn"))
    if ctx.cfg.layer_types is not None:
        both.enter_context(jax.named_scope(KINDS[kind.attn].scope))
    return both


def _attn_input(ctx: Ctx, kind: LayerKind, x, lyr):
    """What the attention sub-layer reads: ``x``'s RMS norm under its gain."""
    with _attn_scopes(ctx, kind), elem():
        return rms_norm(ctx.read(x), ctx.gain(lyr["attn_norm"]),
                        ctx.cfg.norm_eps)


def _attn_sub(ctx: Ctx, kind: LayerKind, x, lyr, residual=True, h=None):
    """The attention sub-layer of ``x`` [B, T, dim]: with its residual, or
    (hyper-connections) its output alone; ``h``: its normed input where the
    block has made it already (``_attn_input``).  The kind makes the heads
    (``AttnKind.heads``: 4-D, or flat from a kind that keeps the layout
    ``wo`` reads); the gate, ``wo`` and the residual are every kind's.
    Shapes derive from ``x`` itself — under pipeline parallelism the block
    sees microbatches, not the full batch."""
    cfg, attn = ctx.cfg, KINDS[kind.attn]
    if h is None:
        h = _attn_input(ctx, kind, x, lyr)
    with _attn_scopes(ctx, kind):
        # [B,T,H,width], or flat [B,T,H*width] from a kind that keeps it so
        o = attn.heads(ctx, kind, h, lyr)
        if cfg.attn_gate:       # times sigmoid(h wg), a scalar a head
            with proj():
                gate = h @ ctx.wc(lyr["wg"])
        with elem():
            if cfg.attn_gate:
                gate = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(ctx.dt)
                o = o * (gate[..., None] if o.ndim == 4 else
                         head_spread(gate, o.shape[-1] // gate.shape[-1]))
            if o.ndim == 4:
                Bb, Tb, heads, width = o.shape
                o = o.reshape(Bb, Tb, heads * width)
        with jax.named_scope("attn.out"):
            out = ctx.red(o @ ctx.wc(lyr["wo"]))
            return ctx.add(x, out) if residual else out


def _route(ctx: Ctx, lyr, h):
    """A routed layer's route from the rows ``h`` its router reads."""
    cfg = ctx.cfg
    return moe_route(lyr, h, cfg.top_k, cfg.norm_topk_prob, cfg.routed_scale,
                     cfg.router_scoring, (cfg.n_group, cfg.topk_group))


def _mlp_sub(ctx: Ctx, kind: LayerKind, x, lyr, residual=True, route=None):
    """``(the FFN sub-layer of x, its weighted auxiliary loss, what it
    counted)``: a routed layer's ``(load, kept)`` (``moe_ffn``'s; ``kept`` is
    None without a group limit), a dense one's None.  ``route``: a routed
    layer's route where the block made it from other rows than these
    (``router_input``)."""
    cfg, dt, wc = ctx.cfg, ctx.dt, ctx.wc
    act = GATE_ACTS[cfg.ffn_act]

    def normed():
        return rms_norm(ctx.read(x), ctx.gain(lyr["mlp_norm"]), cfg.norm_eps)

    with jax.named_scope("mlp"):
        if kind.ffn == SPARSE:
            h = normed()
            out, balance, z, load, kept = moe_ffn(
                lyr, h, top_k=cfg.top_k, compute_dtype=dt,
                dispatch=cfg.moe_dispatch,
                norm_topk_prob=cfg.norm_topk_prob, held=cfg.held,
                routed_scale=cfg.routed_scale,
                aux=bool(cfg.aux_loss_coef or cfg.router_z_loss_coef),
                scoring=cfg.router_scoring, all_load=cfg.rule_bias,
                groups=(cfg.n_group, cfg.topk_group), route=route,
                act=cfg.ffn_act, router_input=cfg.router_input)
            if cfg.shared_expert_hidden:
                out = out + shared_expert(lyr, h, dt, act)
            aux = (cfg.aux_loss_coef * balance
                   + cfg.router_z_loss_coef * z)
            return ((ctx.add(x, out) if residual else out), aux,
                    (load, kept))
        # a dense FFN's two parts (``common.proj`` has what the names are for)
        with jax.named_scope("mlp.up"):
            h = normed()
            gated = act(h @ wc(lyr["w1"])) * (h @ wc(lyr["w3"]))
        with jax.named_scope("mlp.down"):
            out = ctx.red(gated @ wc(lyr["w2"]))
            return ((ctx.add(x, out) if residual else out),
                    jnp.float32(0), None)


def _make_block(ctx: Ctx, kind: LayerKind, tp: int = 1, reduce=None):
    """Build one decoder-layer fn of ``kind`` (with the remat wrapper
    applied).

    ``tp``/``reduce`` specialize it for manual tensor
    parallelism inside a pipeline stage: the block then sees
    tp-local column shards of wq/wk/wv/w1/w3 (so it has ``heads/tp``
    heads and the io width is ``dim/tp``) and ``reduce`` —
    a ``psum`` over the tp axis — completes the row-parallel
    wo/w2 matmuls (the Megatron two-all-reduce-per-layer pattern).
    Default (GSPMD paths): full heads, no explicit collective.
    """
    cfg = ctx.cfg
    if reduce is not None:
        ctx = replace(ctx, tp=tp, red=reduce)

    def block(x, lyr):
        """One decoder layer: attn + residual, MLP/MoE + residual; with
        hyper-connections ``x`` is the n streams (a tuple) and
        each sub-layer reads and writes them through its own gates."""
        if not cfg.hc_mult:
            h = route = None
            if cfg.router_input == "attn" and kind.ffn == SPARSE:
                # the choice is made from the attention's own input, before
                # attention runs; recomputed with the block under remat
                h = _attn_input(ctx, kind, x, lyr)
                with jax.named_scope("route_early"):
                    route = _route(ctx, lyr, h)
            return _mlp_sub(ctx, kind, _attn_sub(ctx, kind, x, lyr, h=h),
                            lyr, route=route)
        pre, post, res = _hc_gates(x, lyr["hc_attn"], cfg)
        x = _hc_write(x, post, res, _attn_sub(ctx, kind, _hc_read(x, pre),
                                              lyr, residual=False))
        pre, post, res = _hc_gates(x, lyr["hc_mlp"], cfg)
        out, aux, counted = _mlp_sub(ctx, kind, _hc_read(x, pre), lyr,
                                     residual=False)
        return _hc_write(x, post, res, out), aux, counted

    if not cfg.remat:
        return block
    # Under scan the body already blocks CSE, so the anti-CSE
    # barriers are pure overhead there.  The flash kernel's
    # custom_vjp composes with checkpoint under both policies.
    if cfg.remat_policy == "dots":
        # Dot outputs PLUS the flash kernel's named (o, lse)
        # residuals (ops/flash_attention.py `_flash_fwd`): with
        # them saved, the backward calls its own flash kernel
        # directly instead of replaying the forward kernel —
        # the recompute tax drops to the cheap tensor ops
        # (norms, rope) for ~one extra o-sized buffer per layer.
        # The grouped schedule's three matmuls are no dot_general
        # either and are saved by name (moe.GROUPED_SAVED); the kind's
        # kernels name theirs (``AttnKind.saved``).
        return jax.checkpoint(
            block,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "wcast", *KINDS[kind.attn].saved, *GROUPED_SAVED)),
            prevent_cse=not cfg.scan_layers)
    if cfg.remat_policy == "full":
        return jax.checkpoint(block, prevent_cse=not cfg.scan_layers)
    raise ValueError(f"unknown remat_policy '{cfg.remat_policy}' "
                     "(expected 'full' or 'dots')")


def _run_pipeline(ctx: Ctx, blocks, layers, x):
    """The layers of ``x`` [B, T, dim] as GPipe runs them over the mesh's
    ``pp`` axis: embed/head stay replicated, the [L, ...] params reshape to
    [pp, L/pp, ...] stages, microbatches ride the schedule in
    parallel/pipeline.py."""
    from ..parallel.pipeline import gpipe

    cfg, mesh, lay = ctx.cfg, ctx.mesh, ctx.cfg.layout
    B, T, _ = x.shape
    if not cfg.scan_layers or cfg.num_experts:
        raise ValueError(
            "pipeline_microbatches requires scan_layers=True and a "
            "dense MLP (num_experts=0)")
    if not lay.uniform:
        raise ValueError(
            "pipeline_microbatches requires every layer alike: stages "
            f"slice one stacked tree, and the layers are {lay.lead} + "
            f"{lay.n_periods} x {lay.period}")
    kind = lay.period[0]
    if int(mesh.shape.get("sp", 1)) > 1:
        # Ring attention's own shard_map cannot nest inside gpipe's.
        raise ValueError(
            "pipeline parallelism composes with dp and tp, not sp "
            "(ring attention inside pipeline stages is unsupported)")
    pp = int(mesh.shape["pp"])
    dp = int(mesh.shape.get("dp", 1))
    tp = int(mesh.shape.get("tp", 1))
    M = cfg.pipeline_microbatches
    if cfg.n_layers % pp or B % (M * dp):
        raise ValueError(
            f"n_layers ({cfg.n_layers}) must divide into pp ({pp}) "
            f"stages and batch ({B}) into {M} microbatches x dp "
            f"({dp}) shards")
    if tp > 1 and cfg.qk_norm:
        raise ValueError(
            "qk_norm inside a pipeline stage with tp > 1 is unsupported: "
            "the stage shards wq/wk by hand and the norm's mean of "
            "squares would need a psum over 'tp'")
    if (kind.heads % tp or (cfg.n_kv_heads or kind.heads) % tp
            or cfg.hidden % tp or cfg.dim % tp):
        raise ValueError(
            f"pp x tp needs n_heads ({kind.heads}), K/V heads "
            f"({cfg.n_kv_heads or kind.heads}), hidden "
            f"({cfg.hidden}) and dim ({cfg.dim}) divisible by tp "
            f"({tp}) — the stage body shards them manually")
    stages = jax.tree_util.tree_map(
        lambda l: l.reshape(pp, cfg.n_layers // pp, *l.shape[1:]), layers)

    if tp > 1:
        # Manual tensor parallelism inside the stage: gpipe's
        # shard_map makes every named axis manual, so the tp layout
        # becomes explicit — column-parallel wq/wk/wv/w1/w3 shards
        # arrive via param_specs, and the block psums the
        # row-parallel wo/w2 outputs over "tp".
        stage_block = _make_block(
            ctx, kind, tp, reduce=lambda t: jax.lax.psum(t, "tp"))
    else:
        stage_block = blocks[kind]

    def stage_fn(stage_params, h):
        def body(h, lyr):
            h, _, _ = stage_block(h, lyr)
            return h, None

        h, _ = jax.lax.scan(body, h, stage_params)
        return h

    # INTERLEAVED microbatch assignment (row r -> microbatch r % M):
    # each microbatch's rows stay evenly spread over the contiguous
    # dp batch shards, so no cross-device reshard per step — a
    # contiguous split would all-to-all the whole activation tensor.
    xm = x.reshape(B // M, M, T, cfg.dim).swapaxes(0, 1)
    with jax.named_scope("layers"):
        xm = gpipe(stage_fn, stages, xm, mesh, axis_name="pp",
                   batch_axis="dp",
                   param_specs=(_layer_pspecs(cfg, mesh) if tp > 1
                                else None))
    return xm.swapaxes(0, 1).reshape(B, T, cfg.dim)


def _run_stack(ctx: Ctx, blocks, layers, x):
    """``(x, weighted auxiliary loss, counted)`` after the layers: a leading
    group, a scan over the periods whose body runs one layer of each slot, a
    trailing part of a period; every layer alike is one slot and nothing
    around the scan.  Without ``scan_layers`` the layers are a list and one
    loop is all there is.  ``counted``: a list of the routed layers' ``(load,
    kept)``, ``[layers, ...]`` an entry, in layer order."""
    cfg, lay = ctx.cfg, ctx.cfg.layout
    aux_total, counted = jnp.float32(0), []
    tmap = jax.tree_util.tree_map
    if cfg.hc_mult:         # the embedding row copied into the n streams
        x = (x,) * cfg.hc_mult

    def run(x, aux, kinds, layers):
        for kind, lyr in zip(kinds, layers):
            x, a, c = blocks[kind](x, lyr)
            aux = aux + a
            if c is not None:
                counted.append(tmap(lambda v: v[None], c))
        return x, aux

    def scan_body(carry, slots):
        x, aux = carry
        run_counted = []
        for (s, count), lyr in zip(lay.runs, slots):
            block = blocks[lay.period[s]]
            if count == 1:
                x, a, c = block(x, lyr)
                aux = aux + a
            else:       # slots alike: one body, scanned

                def alike(carry, lyr, block=block):
                    x, a, c = block(carry[0], lyr)
                    return (x, carry[1] + a), c

                (x, aux), c = jax.lax.scan(alike, (x, aux), lyr)
            run_counted.append(c)
        return (x, aux), tuple(run_counted)

    with jax.named_scope("layers"):
        if not cfg.scan_layers:
            x, aux_total = run(x, aux_total, lay.kinds, layers)
        else:
            lead, period, trail = _grouped(cfg, layers)
            x, aux_total = run(x, aux_total, lay.lead, lead)
            (x, aux_total), run_counted = jax.lax.scan(
                scan_body, (x, aux_total), period)
            routed = [(c, count) for c, (_, count)
                      in zip(run_counted, lay.runs) if c is not None]
            if routed:
                counts = [count for _, count in routed]
                counted.append(tmap(
                    lambda *parts: _layer_order(parts, counts),
                    *[c for c, _ in routed]))
            x, aux_total = run(x, aux_total, lay.period[:lay.n_trail],
                               trail)
    if cfg.hc_mult:         # the streams' mean goes on to the final norm
        x = _hc_mean(x)
    return x, aux_total, counted


def _mtp(ctx: Ctx, block, params, tokens, x, aux_total, counted):
    """``(the prediction module's logits, the auxiliary loss with its
    layer's added)``; what that layer counted is appended to ``counted``.
    One more depth: position t pairs its hidden state ``x`` with the
    embedding of token t+1 and predicts token t+2.  It runs over all T
    positions so that the kernel's blocks divide; the last has no next token
    (a zero row), takes no loss and, being last under a causal mask, moves no
    other position."""
    cfg, dt, gain = ctx.cfg, ctx.dt, ctx.gain
    T = tokens.shape[1]
    with jax.named_scope("mtp"):
        m = params["mtp"]
        nxt = params["embed"][jnp.roll(tokens, -1, axis=1)]
        nxt = nxt.astype(dt) * (jnp.arange(T) < T - 1
                                ).astype(dt)[None, :, None]
        g = jnp.concatenate(
            [rms_norm(x, gain(m["h_norm"]), cfg.norm_eps),
             rms_norm(nxt, gain(m["e_norm"]), cfg.norm_eps)],
            axis=-1) @ m["proj"].astype(dt)
        if cfg.hc_mult:
            g = (g,) * cfg.hc_mult
        g, a, c = block(g, m["layer"])
        aux_total = aux_total + a
        if c is not None:
            counted.append(jax.tree_util.tree_map(lambda v: v[None], c))
        if cfg.hc_mult:
            g = _hc_mean(g)
        with jax.named_scope("head"):
            g = rms_norm(g, gain(m["out_norm"]), cfg.norm_eps)
            return g @ params["head"].astype(dt), aux_total


def _ce_value(logits, targets):
    lf = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - ll)


@jax.custom_vjp
def _ce(logits, targets):
    return _ce_value(logits, targets)


def _ce_fwd(logits, targets):
    return _ce_value(logits, targets), (logits, targets)


def _ce_bwd(res, g):
    # dlogits = (softmax − onehot)/N · g, computed in f32 then cast back
    # to the LOGITS' dtype.  Without this vjp the cotangent inherits the
    # f32 of the loss math, and the whole head backward (the two largest
    # matmuls in the model at vocab 32k) runs f32 at half MXU rate; in
    # f32 compute mode the cast is the identity, so fp32 parity checks
    # are untouched.  one_hot lowers to an iota-compare that XLA fuses
    # into the elementwise (p−onehot)·scale pass — a scatter formulation
    # was measured 4% SLOWER end-to-end on v5e.
    logits, targets = res
    B, T, V = logits.shape
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    d = (p - jax.nn.one_hot(targets, V, dtype=jnp.float32)) * (g / (B * T))
    return d.astype(logits.dtype), None


_ce.defvjp(_ce_fwd, _ce_bwd)


def lm_loss(params, tokens, cfg: TransformerConfig,
            mesh: Optional[Mesh] = None):
    """Next-token cross-entropy, mean over all positions (float32).

    MoE configs add their weighted auxiliary loss (``transformer_forward``).

    Two CE lowerings, picked by head size.  Both of ``BENCHMARK.json``'s
    uniform ``lm_train`` configurations (vocab 49,152 and 50,304) take the
    ``_ce`` custom_vjp, whose bf16 dlogits keep the model's two largest
    matmuls on the MXU fast path.  Earlier rounds measured it to win
    from vocab 16k up and to LOSE 40% end-to-end on a small head (dim
    512 / vocab 8k) because the vjp boundary blocks XLA from fusing the
    CE backward, and those extra HBM passes dwarf the cheap matmul's
    dtype win.  The one head in between, 3072 x 12,544 (the Laguna cell's
    slice of its vocabulary), was run both ways on the v5e (PR 30, one
    seed, median of 10 steps of 1 x 8192): ``_ce`` 0.42367 s a step,
    ``_ce_value`` 0.42753 s, so the vjp wins there too, by 0.9%, and the
    crossover stands at 12,288 (ROADMAP Design 2).  A 3584 x 16,384 head
    (one chip's eighth of a 131,072-id vocabulary) is above it and takes
    ``_ce``; where a prediction module (``mtp_layers``) takes a second
    cross-entropy from the same head, that one takes the same lowering."""
    return _loss_and_routes(params, tokens, cfg, mesh)[0]


def _loss_and_routes(params, tokens, cfg: TransformerConfig,
                     mesh: Optional[Mesh] = None):
    """``(lm_loss, routes)``: ``routes`` is the routed layers' counted
    routes (``expert_load``'s array) where the configuration holds a share
    of the experts (``cfg.counts_routes``), else ``None``."""
    loss, (routes, _, _) = _loss_routes_loads(params, tokens, cfg, mesh)
    return loss, routes


def _loss_routes_loads(params, tokens, cfg: TransformerConfig,
                       mesh: Optional[Mesh] = None):
    """``(lm_loss, (routes, loads, kept))``: ``_loss_and_routes`` and, where
    the step moves a router bias by rule (``cfg.rule_bias``), every expert's
    counted routes ``[routed layers (+ the prediction module's), E]``, else
    ``None``; ``kept`` is ``_forward``'s, where the step counts routes."""
    ce, ce_mtp, aux, load, kept = _ce_parts(params, tokens, cfg, mesh)
    if ce_mtp is not None:
        ce = ce + cfg.mtp_loss_coef * ce_mtp
    return (ce + aux if cfg.num_experts else ce,
            (_routes(cfg, load) if cfg.counts_routes else None,
             load if cfg.rule_bias else None,
             kept if cfg.counts_routes else None))


def _ce_parts(params, tokens, cfg: TransformerConfig,
              mesh: Optional[Mesh] = None):
    """``(next-token cross-entropy, the prediction module's or None,
    weighted auxiliary loss, load)``."""
    logits, aux, load, mtp_logits, kept = _forward(params, tokens, cfg, mesh)
    # Crossover measured between 8192 (big loss at dim 512) and 12,544 (a
    # small win at dim 3072).
    ce_fn = _ce if cfg.vocab_size >= 12288 else _ce_value
    with jax.named_scope("loss"):
        if cfg.n_pred_heads > 1:
            # head i's mean over the positions that have token t + 1 + i,
            # the heads weighted alike
            V, T = cfg.vocab_size, tokens.shape[1]
            ce = sum(ce_fn(logits[:, :T - 1 - i, i * V:(i + 1) * V],
                           tokens[:, 1 + i:])
                     for i in range(cfg.n_pred_heads)) / cfg.n_pred_heads
        else:
            ce = ce_fn(logits[:, :-1], tokens[:, 1:])
    ce_mtp = None
    if mtp_logits is not None:       # position t predicts token t + 2
        with jax.named_scope("mtp"), jax.named_scope("loss"):
            ce_mtp = ce_fn(mtp_logits[:, :-2], tokens[:, 2:])
    return ce, ce_mtp, aux, load, kept


def _ruled(path) -> bool:
    """Whether the leaf at ``path`` is one a rule moves and no updater."""
    return getattr(path[-1], "key", None) == "router_bias"


def _bias_rule(cfg: TransformerConfig, params, loads):
    """``params`` with every ``router_bias`` moved by the rule ``b_e +=
    router_bias_rate * sign(mean(load) - load_e)``, ``loads [routed layers
    (+ the prediction module's), E]`` the routes this step counted, in layer
    order.  The deployment sums the load over the chips that share the
    data; here it is this program's tokens."""
    lay = cfg.layout
    with jax.named_scope("update"):
        loads = loads.astype(jnp.float32)
        move = cfg.router_bias_rate * jnp.sign(
            jnp.mean(loads, axis=1, keepdims=True) - loads)

        def moved(lyr, rows):
            return {**lyr, "router_bias": lyr["router_bias"] + move[rows]}

        rows = iter(range(loads.shape[0]))
        row_of = [next(rows) if k.ffn == SPARSE else None for k in lay.kinds]
        at = lambda i, lyr: (lyr if row_of[i] is None
                             else moved(lyr, row_of[i]))
        layers = params["layers"]
        if not cfg.scan_layers:
            layers = [at(i, lyr) for i, lyr in enumerate(layers)]
        else:
            lead, period, trail = _grouped(cfg, layers)
            n_lead, p = len(lay.lead), len(lay.period)
            # a run's rows, shaped as its leaves are stacked
            rows_at = np.asarray([-1 if r is None else r for r in row_of])
            period = [
                slot if lay.period[s].ffn != SPARSE else moved(
                    slot, rows_at[lay.run_layers(s, count)])
                for (s, count), slot in zip(lay.runs, period)]
            layers = (period[0] if lay.uniform else {
                "lead": [at(i, lyr) for i, lyr in enumerate(lead)],
                "period": period,
                "trail": [at(n_lead + p * lay.n_periods + i, lyr)
                          for i, lyr in enumerate(trail)]})
        params = {**params, "layers": layers}
        if cfg.mtp_layers and lay.kinds[-1].ffn == SPARSE:
            params["mtp"] = {**params["mtp"],
                             "layer": moved(params["mtp"]["layer"], -1)}
    return params


class TransformerTrainer:
    """Mesh-parallel LM training through the framework's updaters.

    The parameter pytree is the "table": sharded master weights in float32,
    updated in place by the same Updater the tables use — the reference's
    server-side optimizer semantics at transformer scale.
    """

    def __init__(self, cfg: TransformerConfig, mesh: Mesh,
                 updater_type: str = "sgd",
                 option: Optional[AddOption] = None,
                 seed: int = 0):
        self.cfg = cfg
        self.mesh = mesh
        self.updater = get_updater(updater_type)
        self.option = option or AddOption(learning_rate=0.1)
        # Start-up sections (docs/observability.md, "Start-up"): the draw of
        # every leaf on its own sharding (each device of a replicated leaf
        # draws its own copy), then the state's zeros, each to its
        # completion.
        with dashboard.monitor("Transformer::init_draw"):
            self.params = jax.block_until_ready(
                init_params(cfg, seed, param_shardings(cfg, mesh)))
        with dashboard.monitor("Transformer::init_place"):
            self.state = jax.tree_util.tree_map(
                lambda p: tuple(jnp.zeros_like(p)
                                for _ in range(self.updater.num_slots)),
                self.params)
            jax.block_until_ready(self.state)
        self._step = None
        # The last step's counted routes, on the device (``cfg.
        # counts_routes``: int32 [routed layers, experts_held + 1], the held
        # experts' routes and the routes that went elsewhere), else None;
        # and, where groups limit the router's choice too, the tokens of each
        # routed layer that kept the held experts' group (int32 [routed
        # layers]), else None.
        self.routes = self.kept = None
        self._steps_dispatched = 0     # the ``step`` of mv.trainer.dispatch
        self._eval = None
        self._eval_parts = None
        self._balance = None
        self._offload = None  # (bridge, leaf shapes/shardings) — see below

    def _apply_updates(self, params, state, grads):
        """One updater application over the whole param pytree."""
        updater, opt = self.updater, self.option
        with_path, tree = jax.tree_util.tree_flatten_with_path(params)
        flat_p = [p for _, p in with_path]
        # A leaf a rule moves (``_bias_rule``) is not the updater's.
        ruled = [_ruled(path) for path, _ in with_path]
        flat_s = tree.flatten_up_to(state)
        flat_g = tree.flatten_up_to(grads)
        with jax.named_scope("update"):
            out = [(p, s) if r else updater.apply_dense(p, s, g, opt)
                   for p, s, g, r in zip(flat_p, flat_s, flat_g, ruled)]
        params = jax.tree_util.tree_unflatten(tree, [p for p, _ in out])
        state = jax.tree_util.tree_unflatten(tree, [s for _, s in out])
        return params, state

    def _raw_step(self, accum: int = 1):
        """Un-jitted (params, state, tokens) -> (params, state, loss,
        routes, kept); ``routes`` is ``None`` unless ``cfg.counts_routes``,
        ``kept`` unless groups limit the router's choice as well.

        ``accum > 1`` splits the batch into that many microbatches,
        accumulates their gradients in float32 (a ``lax.scan`` so the
        activation memory is ONE microbatch's), and applies a single
        update — mathematically the full-batch step (the CE is a mean
        over equal-size chunks), with the activation footprint of
        ``batch/accum``.  The trade is an extra f32 grad accumulator of
        one full parameter set riding the scan carry, so the knob pays
        off on ACTIVATION-dominated configs (long context, few params);
        at 0.96B parameters the carry (~3.9 GB) was measured in an
        earlier round to eat the whole 16 GB headroom the smaller
        microbatch freed.  The
        microbatch must still be divisible by the mesh's dp axis.
        MoE configs are rejected: their load-balancing aux loss is a
        product of batch MEANS (nonlinear in the batch), so microbatching
        would silently change the training objective, not just its
        memory profile."""
        cfg, mesh = self.cfg, self.mesh
        if accum > 1 and cfg.num_experts:
            raise ValueError(
                "grad accumulation is not equivalence-preserving for MoE "
                "configs (batch-nonlinear aux loss); run MoE at full batch")

        def step(params, state, tokens):
            routes = loads = kept = None
            if accum == 1:
                (loss, (routes, loads, kept)), grads = jax.value_and_grad(
                    _loss_routes_loads, has_aux=True)(params, tokens, cfg,
                                                      mesh)
            else:
                B, T = tokens.shape
                if B % accum:
                    raise ValueError(
                        f"batch {B} not divisible by accum {accum}")
                dp = int(mesh.shape.get("dp", 1)) if mesh is not None else 1
                if (B // accum) % dp:
                    raise ValueError(
                        f"microbatch {B // accum} (batch {B} / accum "
                        f"{accum}) not divisible by the dp axis ({dp})")
                chunks = tokens.reshape(accum, B // accum, T)

                def body(g_acc, chunk):
                    li, gi = jax.value_and_grad(lm_loss)(params, chunk,
                                                         cfg, mesh)
                    g_acc = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32), g_acc, gi)
                    return g_acc, li

                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                g_sum, losses = jax.lax.scan(body, zeros, chunks)
                grads = jax.tree_util.tree_map(
                    lambda g: (g / accum), g_sum)
                loss = jnp.mean(losses)
            params, state = self._apply_updates(params, state, grads)
            if loads is not None:
                params = _bias_rule(cfg, params, loads)
            return params, state, loss, routes, kept

        return step

    def balance_router_bias(self, tokens, steps: int) -> None:
        """``steps`` applications of the bias rule alone on ``tokens``
        [B, T]: a forward pass counts every routed layer's load and
        ``_bias_rule`` moves the biases; no weight moves.  With the weights
        still, the rule walks the loads toward their mean at its own speed
        (``router_bias_rate`` a pass), which in training it does against a
        router that keeps learning."""
        if not self.cfg.rule_bias:
            raise ValueError("balance_router_bias: the configuration has no "
                             "router bias (router_scoring='sigmoid')")
        if self._balance is None:
            cfg, mesh = self.cfg, self.mesh
            self._balance = jax.jit(
                lambda p, t: _bias_rule(cfg, p,
                                        _ce_parts(p, t, cfg, mesh)[3]),
                donate_argnums=(0,))
        with dashboard.monitor("Transformer::balance_router_bias",
                               steps=steps):
            tokens = jnp.asarray(tokens, jnp.int32)
            for _ in range(steps):
                self.params = self._balance(self.params, tokens)
            jax.block_until_ready(self.params)

    def route_rows(self) -> Optional[np.ndarray]:
        """Of the last step, the rows each routed layer's route buffers held
        over the layer's ``N*k`` routes (float ``[routed layers]``, from
        ``routes``; ``None`` where the step counts none, or the schedule is
        not ``grouped``): the rung of ``moe.route_rungs`` that held the
        layer's held routes.  About the held share where the buffers followed
        the routes, 1.0 where every route's rows were walked."""
        cfg = self.cfg
        if self.routes is None or cfg.moe_dispatch != "grouped":
            return None
        routes = np.asarray(self.routes, np.int64)
        of, held = routes.sum(axis=1), routes[:, :-1].sum(axis=1)
        rungs = np.asarray(route_rungs(int(of[0]), cfg.experts_held,
                                       cfg.num_experts))
        return rungs[np.searchsorted(rungs, held)] / of

    def router_bias_absmax(self) -> float:
        """The largest ``|router_bias|`` of any routed layer (0.0 where the
        configuration has none): how far the rule has moved the choice."""
        with_path, _ = jax.tree_util.tree_flatten_with_path(self.params)
        found = [jnp.max(jnp.abs(leaf)) for path, leaf in with_path
                 if _ruled(path)]
        return float(max(found)) if found else 0.0

    # ------------------------------------------------------ state offload
    def offload_state(self, bridge) -> None:
        """Move the optimizer state to a remote store (ZeRO-style
        offload over the host bridge, docs/host_bridge.md).

        ``bridge`` is a :class:`~multiverso_tpu.parallel.OffloadedState`
        sized to the flat state element count (``offload_size()``) whose
        backing fleet runs ``-updater_type=assign`` — the bridge is a
        bit-exact store, so the offloaded run's loss trajectory matches
        the in-memory baseline bit for bit (``make bridge-demo``
        asserts exactly that).  After this call, ``train_step_async``
        round-trips the state each step: fetch the prefetched vector,
        rebuild the device pytree, step, push the new state async and
        prefetch the next — the wire rides behind the tail of the
        step's device execution instead of serializing with it.  The
        trade is host<->device traffic of one state set per step for
        state that no longer occupies device memory between steps."""
        leaves = jax.tree_util.tree_leaves(self.state)
        if not leaves:
            raise ValueError(
                f"updater '{self.updater.name}' keeps no optimizer "
                f"state — nothing to offload")
        if bridge.size != self.offload_size():
            raise ValueError(
                f"bridge sized {bridge.size}, state needs "
                f"{self.offload_size()} elements")
        self._offload = bridge
        bridge.init(self._state_to_flat())
        # The device copies now live remotely; drop them so the memory
        # relief is real (rebuilt from the bridge on the next step).
        self.state = jax.tree_util.tree_map(
            lambda p: tuple(None for _ in range(self.updater.num_slots)),
            self.params)
        bridge.prefetch()

    def offload_size(self) -> int:
        """Flat float32 element count of the optimizer state — the
        ``OffloadedState`` size this trainer needs."""
        return int(sum(np.prod(p.shape)
                       for p in jax.tree_util.tree_leaves(self.params))
                   ) * self.updater.num_slots

    def _state_to_flat(self, state=None) -> np.ndarray:
        leaves = jax.tree_util.tree_leaves(
            self.state if state is None else state)
        out = np.empty(self.offload_size(), np.float32)
        pos = 0
        for leaf in leaves:
            n = int(np.prod(leaf.shape))
            np.copyto(out[pos:pos + n],
                      np.asarray(leaf, np.float32).ravel())
            pos += n
        return out

    def _flat_to_state(self, flat: np.ndarray):
        """Rebuild the sharded state pytree from the bridge's vector
        (device_put per leaf with the matching param sharding)."""
        flat_p, tree = jax.tree_util.tree_flatten(self.params)
        pos = 0
        slots_per = self.updater.num_slots
        rebuilt = []
        for p in flat_p:
            n = int(np.prod(p.shape))
            slots = []
            for _ in range(slots_per):
                host = flat[pos:pos + n].reshape(p.shape)
                slots.append(jax.device_put(host, p.sharding))
                pos += n
            rebuilt.append(tuple(slots))
        return jax.tree_util.tree_unflatten(tree, rebuilt)

    def _jitted_step(self, accum: int = 1):
        """``(jitted step, batch placer)`` for this accum value."""
        if self._step is None:
            self._step = {}
        if accum not in self._step:
            from ..parallel.sharding import batch_placer

            _, place = batch_placer(self.mesh, "dp", dtype=jnp.int32)
            step = jax.jit(self._raw_step(accum), donate_argnums=(0, 1))
            self._step[accum] = (step, place)
        return self._step[accum]

    def lowered_step(self, tokens, accum: int = 1,
                     lowering_platforms=None):
        """``jax.stages.Lowered`` of the program ``train_step_async``
        runs for this batch.  The attention body (Mosaic kernel or jnp
        path) is fixed at trace time, so this is where to read which one
        a compiled step holds (``.as_text()``) and to compile ahead of
        the first step (``.compile()``).  ``lowering_platforms`` lowers
        for another platform than the one the arrays live on
        (``("tpu",)`` from a CPU host: text only, it cannot compile)."""
        step, place = self._jitted_step(accum)
        traced = step.trace(self.params, self.state, place(tokens))
        return traced.lower(lowering_platforms=lowering_platforms)

    def train_step_async(self, tokens, accum: int = 1) -> jax.Array:
        """Enqueue one step; returns the device loss scalar (no host
        sync).  Back-to-back callers (the bench loop) pipeline dispatches
        and fetch once at the end, so the host never stalls the device
        between steps.

        ``accum`` > 1 runs the gradient-accumulation step (see
        ``_raw_step``): one update from ``accum`` microbatches with a
        single microbatch's activation memory.  Compiled steps are
        cached PER accum value, so interleaving regimes does not
        recompile."""
        step, place = self._jitted_step(accum)
        with tracing.span("mv.trainer.place"):
            placed = place(tokens)
        dispatch = tracing.span("mv.trainer.dispatch",
                                step=self._steps_dispatched)
        self._steps_dispatched += 1
        if self._offload is None:
            with dispatch:
                self.params, self.state, loss, self.routes, self.kept = step(
                    self.params, self.state, placed)
            return loss
        # Offloaded state (docs/host_bridge.md): the vector prefetched
        # during the previous step's tail is ready (or fetched now on
        # the first step), rebuilt on device, donated into the step;
        # the new state ships back ASYNC and the next prefetch rides
        # behind it (FIFO) while the caller moves on.
        with dashboard.monitor("Transformer::offload_wait"):
            state = self._flat_to_state(self._offload.wait())
        with dispatch:
            self.params, new_state, loss, self.routes, self.kept = step(
                self.params, state, placed)
        with dashboard.monitor("Transformer::offload_push"):
            self._offload.push(self._state_to_flat(new_state))
            self._offload.prefetch()
        del new_state  # device copies die; the remote store owns them
        return loss

    def train_step(self, tokens) -> float:
        with dashboard.monitor("Transformer::train_step"):
            return float(self.train_step_async(tokens))

    def loss(self, tokens) -> float:
        if self._eval is None:
            cfg, mesh = self.cfg, self.mesh
            self._eval = jax.jit(
                lambda p, t: lm_loss(p, t, cfg, mesh))
        return float(self._eval(self.params,
                                jnp.asarray(tokens, jnp.int32)))

    def loss_parts(self, tokens) -> Tuple[float, Optional[float]]:
        """``(next-token cross-entropy, the prediction module's or None)``
        of ``tokens``, unweighted: the two terms ``loss`` sums."""
        if self._eval_parts is None:
            cfg, mesh = self.cfg, self.mesh
            self._eval_parts = jax.jit(
                lambda p, t: _ce_parts(p, t, cfg, mesh)[:2])
        ce, ce_mtp = self._eval_parts(self.params,
                                      jnp.asarray(tokens, jnp.int32))
        return float(ce), None if ce_mtp is None else float(ce_mtp)

    # ------------------------------------------------------------ checkpoint
    def save(self, uri: str) -> None:
        """Snapshot params + updater state (collective; rank-0 atomic
        write — same durability as the table checkpoints).  With the
        state offloaded, it is re-materialized from the bridge first
        (the next step's wait simply pays one blocking fetch)."""
        from .. import checkpoint

        state = self.state
        if self._offload is not None:
            state = self._flat_to_state(self._offload.wait())
        checkpoint.save_pytree(uri, {"params": self.params,
                                     "state": state})

    def restore(self, uri: str) -> None:
        """Load a snapshot onto THIS trainer's mesh/shardings (the
        writing mesh need not match — leaves re-place by the current
        params' shardings)."""
        from .. import checkpoint

        like_state = self.state
        if self._offload is not None:
            # Offloaded runs keep no device state; restore against a
            # zeros-like template, then re-seed the remote store.
            like_state = jax.tree_util.tree_map(
                lambda p: tuple(jnp.zeros_like(p)
                                for _ in range(self.updater.num_slots)),
                self.params)
        snap = checkpoint.restore_pytree(
            uri, like={"params": self.params, "state": like_state})
        self.params = snap["params"]
        if self._offload is not None:
            self._offload.init(self._state_to_flat(snap["state"]))
            self._offload.prefetch()
        else:
            self.state = snap["state"]
