"""Plain reference of SmallThinker-21BA3B-Instruct's decoder
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json),
the model ``lm_train_route_first`` trains for the configuration
``smallthinker-21b-a3b-l4-e64``.  Every layer alike but for two lists, d =
``hidden_size`` 2560, D = ``head_dim`` 128, H = 28 query heads, KV = 4 K/V
heads, E = 64 experts of width 768, k = 6; x [T, d]:

    h  = RMSNorm(x; g_attn)                                   eps 1e-6
    r  = h Wr                                   [T, E], float32: the router
         reads the ATTENTION sub-layer's normed input ("router placed before
         attention"), so the choice is made before attention runs
    (r_1..r_k, e_1..e_k) = top_k(r);   w = softmax(r_1..r_k)
         (moe_primary_router_apply_softmax, norm_topk_prob: a softmax over
         the k chosen logits IS a softmax over all E, top-k, renormalised)
    q  = h Wq [T, H, D]    k = h Wk [T, KV, D]    v = h Wv [T, KV, D]
    rope_layout[l] = 1: all D dims of q and k rotated, theta 1.5e6
    rope_layout[l] = 0: NO position embedding at all
    query head j reads K/V head j // (H / KV)                    (7 a head)
    sliding_window_layout[l] = 1: key s visible to t  iff  t - 4096 < s <= t
    sliding_window_layout[l] = 0:                     iff  s <= t
    o  = softmax(q k^T / sqrt(D) + mask) v
    x' = x + o.reshape(T, H * D) Wo
    u  = RMSNorm(x'; g_mlp)
    y  = sum_{j=1..k} w_j W2[e_j] (relu(W1[e_j] u) * (W3[e_j] u))    "ReGLU"
    x_next = x' + y
    loss = mean CE of RMSNorm(x_L; g_out) W_head over the next tokens

The published period is 4: layer 0 of a period is full and unrotated (the
program's kind ``full_attention_nope``), layers 1-3 are windowed and rotated
(``sliding_attention``); the two lists always agree, so a layer's kind says
both.  Written from these equations, in float32 under
``jax.default_matmul_precision("highest")``: a dense ``[T, T]`` mask and
scores, one query head at a time (a ``lax.map`` over the 28 heads whose body
is recomputed in the backward, so that 8,192 tokens at the published widths
fit beside the program's weights: one head's scores are 256 MiB); the routed
FFN a loop over all 64 experts (a ``lax.scan`` over the expert axis of the
weights) in which every expert computes every token and the route's weight,
zero where the token did not choose it, keeps what was routed.  No kernel, no
sort, no grouped matmul, no cache.

It reads the program's parameter tree (``embed``, ``out_norm``, ``head``,
``layers`` as ``multiverso_tpu.models.transformer.group_layers`` holds them)
and the program's rotary layout (the rotated dims split in halves ``x1 | x2``,
frequency i of 64 is ``theta ** (-i / 64)``, positions from 0).  ``model`` is
the configuration's ``model`` group.

Departures from the published description: none known.  Where the config is
silent (``assumed`` in the configuration's file): the router reads the normed
input ``h`` and not the raw stream ``x``; there are no secondary experts (the
config has keys for primary ones alone); ReLU is on the ``W1`` branch; the
window includes the query's own key; no bias, no QK norm, no auxiliary loss.
The release's ``modeling_smallthinker.py`` would settle the first three and
could not be read here.

**Controls** (``control=`` of ``loss`` / ``loss_and_grads``; what the limits
below have to refuse, ``python -m benchmarks.runners.lm_train_route_first
--seeds ...`` prints each): ``router_input="mlp"`` (the router reads ``u``),
``act="silu"``, ``rope_full=True`` (rotary on the full layers too),
``window_off=True`` (the band as full causal), ``routing_dtype`` (the
router's input and matmul in bfloat16), ``softmax_dtype`` (the attention
scores and their softmax in bfloat16) and ``weights_dtype`` (every matrix
rounded through the precision below the configuration's bfloat16, float8
e4m3).

Tolerances: ``LOSS_ATOL``, ``GRAD_RTOL`` and ``GRAD_RTOL_ROUTED`` below, each
with the chip readings it was set from (``PERF.md`` section 6, PR 48).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["loss", "loss_and_grads", "layer", "routed", "LOSS_ATOL",
           "GRAD_RTOL", "GRAD_RTOL_ROUTED"]

# Set from chip readings at the published widths, 4 layers, one sequence of
# 8,192 Zipf tokens, rate 0.002 (my chip run, PR 48; nine seeds: 41, 42
# and the seven runs of the cell; PERF.md section 6 has the table):
# - LOSS_ATOL: |program - reference| read 3.8e-4 .. 1.8e-3 at a loss of 10.3;
#   the bound is ``dense_lm.py``'s, as every cell's (5 times the largest
#   reading).  Of the controls SiLU for ReLU moves the loss past it (2.8e-2);
#   the others pass here (rotary on the full layer 9.8e-3, float8 weights
#   6.5e-3, the router on the FFN's input 4.2e-3) and are caught by a gradient.
# - GRAD_RTOL, every sampled leaf outside the routed experts' path (norm
#   gains, tiles of wq / wk / wv / wo of the unrotated and of a windowed layer,
#   embedding rows, the final norm gain): relative L2 distance between
#   (old - new) / lr and this file's gradient.  The worst leaf read 4.3-6.1%
#   over the nine seeds (``L1.mlp_norm``, ``L1.wk``).  The same step against
#   this file under a control (seed 41): the band as full causal 15.4% (half of
#   the checked queries have lost keys to the band), float8 weights 25.6%, SiLU
#   for ReLU 49%, rotary on the full layer 125%, the router on the FFN's input
#   165%.  The bound is 11%: 1.8 times the largest reading, 0.71 of the
#   nearest control's.
# - GRAD_RTOL_ROUTED, the routers and every expert's w1 / w3 / w2 tiles: they
#   take each route that the program's bfloat16 hidden state sends elsewhere
#   than this file's float32 one (both right: a token's 6th and 7th logits lie
#   closer than bfloat16 moves them), at a weight of about 1/6.  The worst read
#   5.8-8.8% over the nine seeds (a router); float8 weights 28.3%, SiLU 83%,
#   rotary 129%, the router on the FFN's input 157% (the band as full causal
#   12.7%: caught by GRAD_RTOL, not here).  The bound is 17%: 1.9 times the
#   largest reading, 0.6 of float8's.
# Not refused by either (they read as the program does, whose hidden state IS
# bfloat16): ``routing_dtype`` bfloat16 (5.0 / 5.4%) and ``softmax_dtype``
# bfloat16 (5.1 / 6.3%); PERF.md section 7.
LOSS_ATOL = 1e-2
GRAD_RTOL = 0.11
GRAD_RTOL_ROUTED = 0.17

NOPE, SLIDING = "full_attention_nope", "sliding_attention"


def layer(layers, i: int):
    """Layer ``i``'s own leaves out of the program's ``layers`` tree."""
    if isinstance(layers, (list, tuple)):
        return layers[i]
    if "period" not in layers:
        return {k: v[i] for k, v in layers.items()}
    lead, period, trail = layers["lead"], layers["period"], layers["trail"]
    if i < len(lead):
        return lead[i]
    j = i - len(lead)
    repeats = jax.tree_util.tree_leaves(period[0])[0].shape[0]
    if j < len(period) * repeats:
        return jax.tree_util.tree_map(lambda v: v[j // len(period)],
                                      period[j % len(period)])
    return trail[j - len(period) * repeats]


def routed(leaf: str) -> bool:
    """Whether a sampled leaf lies on the routed experts' path."""
    return leaf.rsplit(".", 1)[-1] in ("router", "w1", "w2", "w3")


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _rotary(x, theta: float):
    """x [B, T, heads, D], all D dims rotated."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _route(rows, router, st):
    """``(weights [N, k], experts [N, k])``: the k largest logits, softmax
    over those k."""
    if st["routing_dtype"] is not None:            # a control: see the header
        dt = st["routing_dtype"]
        logits = (rows.astype(dt) @ router.astype(dt)).astype(jnp.float32)
    else:
        logits = rows @ router
    top_r, top_e = jax.lax.top_k(logits, st["top_k"])
    return jax.nn.softmax(top_r, axis=-1), top_e


def _attention(x, h, lyr, st, attn: str):
    """``x + o Wo`` for ``h = RMSNorm(x)``; x [B, T, d]."""
    B, T, _ = x.shape
    H, KV, D = st["heads"], st["kv_heads"], st["head_dim"]
    q = (h @ lyr["wq"]).reshape(B, T, H, D)
    k = (h @ lyr["wk"]).reshape(B, T, KV, D)
    v = (h @ lyr["wv"]).reshape(B, T, KV, D)
    if attn == SLIDING or st["rope_full"]:
        q, k = _rotary(q, st["theta"]), _rotary(k, st["theta"])
    t = jnp.arange(T)
    visible = t[None, :] <= t[:, None]
    if attn == SLIDING and not st["window_off"]:
        visible = visible & (t[None, :] > t[:, None] - st["window"])
    sdt = st["softmax_dtype"] or jnp.float32

    def one_head(j):
        kv = j // (H // KV)
        s = jnp.einsum("btd,bsd->bts", q[:, :, j].astype(sdt),
                       k[:, :, kv].astype(sdt)) * D ** -0.5
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", p,
                          v[:, :, kv].astype(sdt)).astype(jnp.float32)

    o = jax.lax.map(jax.checkpoint(one_head), jnp.arange(H))   # [H, B, T, D]
    return x + o.transpose(1, 2, 0, 3).reshape(B, T, H * D) @ lyr["wo"]


def _experts(u, lyr, weights, experts, st):
    """``sum_j w_j E[e_j](u)``, u [N, d]: every expert on every token, times
    the route's weight (zero where the token did not choose the expert)."""
    def act(a):
        if st["act"] == "silu":                    # a control
            return jax.nn.silu(a)
        return jnp.where(a > 0, a, 0.0)

    def contribution(e, w1, w3, w2):
        weight = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return weight[:, None] * ((act(u @ w1) * (u @ w3)) @ w2)

    def one_expert(y, expert):
        return y + jax.checkpoint(contribution)(*expert), None

    E = lyr["w1"].shape[0]
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(u),
                        (jnp.arange(E), lyr["w1"], lyr["w3"], lyr["w2"]))
    return y


def _block(x, lyr, statics, attn):
    st = dict(statics)
    B, T, d = x.shape
    if st["weights_dtype"] is not None:            # a control
        lyr = {k: (v.astype(st["weights_dtype"]).astype(jnp.float32)
                   if v.ndim > 1 else v) for k, v in lyr.items()}
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, lyr["attn_norm"], st["eps"])
        early = st["router_input"] == "attn"
        if early:               # chosen before attention, from its own input
            weights, experts = _route(h.reshape(B * T, d), lyr["router"], st)
        x = _attention(x, h, lyr, st, attn)
        u = _rms_norm(x, lyr["mlp_norm"], st["eps"]).reshape(B * T, d)
        if not early:                              # a control
            weights, experts = _route(u, lyr["router"], st)
        return x + _experts(u, lyr, weights, experts, st).reshape(B, T, d)


_block_jit = jax.jit(_block, static_argnames=("statics", "attn"))


def _ce(x, out_norm, head, tokens, eps, weights_dtype):
    """Mean next-token cross-entropy from the last hidden states."""
    if weights_dtype is not None:
        head = head.astype(weights_dtype).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = _rms_norm(x, out_norm, eps) @ head              # [B, T, V]
    logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                                 axis=-1)[..., 0]
    return jnp.mean(logz - picked)


_ce_jit = jax.jit(_ce, static_argnames=("eps", "weights_dtype"))

_CONTROLS = {"router_input": "attn", "act": "relu", "rope_full": False,
             "window_off": False, "routing_dtype": None,
             "softmax_dtype": None, "weights_dtype": None}


def _statics(model: dict, control):
    """The layer function's static arguments (hashable), and the kinds."""
    unknown = set(control or {}) - set(_CONTROLS)
    if unknown:
        raise ValueError(f"unknown controls {sorted(unknown)}")
    kinds = list(model["layer_types"])
    if set(kinds) - {NOPE, SLIDING}:
        raise ValueError(f"layers of kinds {sorted(set(kinds))}: this "
                         f"reference knows {NOPE} and {SLIDING}")
    statics = dict(
        heads=model["n_heads"], kv_heads=model["n_kv_heads"],
        head_dim=model["head_dim"], eps=float(model["norm_eps"]),
        window=int(model["sliding_window"]),
        theta=float(model["rope_sliding"]["theta"]),
        top_k=model["top_k"], **dict(_CONTROLS, **(control or {})))
    return tuple(sorted(statics.items())), kinds


def loss(params, tokens, model, control=None):
    """Mean cross-entropy over every next-token position of ``tokens``
    [B, T]."""
    statics, kinds = _statics(model, control)
    x = params["embed"][tokens]
    for i, attn in enumerate(kinds):
        x = _block_jit(x, layer(params["layers"], i), statics=statics,
                       attn=attn)
    return _ce_jit(x, params["out_norm"], params["head"], tokens,
                   eps=float(model["norm_eps"]),
                   weights_dtype=dict(statics)["weights_dtype"])


def loss_and_grads(params, tokens, model, layers=(0,), control=None):
    """``(loss, grads)`` with gradients for ``embed``, ``out_norm`` and every
    leaf of the layers named (``grads["layers"][i]``, a dict without the
    layer axis): the leaves the runner samples.  Reverse mode is ``jax.vjp``
    of the plain functions above, chained over the layers by hand: each
    layer's forward runs again in the backward, one layer's activations are
    held at a time, and the other layers' weight gradients are not formed."""
    statics, kinds = _statics(model, control)
    eps = float(model["norm_eps"])
    wdt = dict(statics)["weights_dtype"]
    xs = [params["embed"][tokens]]
    for i, attn in enumerate(kinds):
        xs.append(_block_jit(xs[-1], layer(params["layers"], i),
                             statics=statics, attn=attn))
    ce, pull = jax.vjp(
        lambda x, g: _ce_jit(x, g, params["head"], tokens, eps=eps,
                             weights_dtype=wdt),
        xs.pop(), params["out_norm"])
    dx, d_norm = pull(jnp.ones_like(ce))
    grads = {"out_norm": d_norm, "layers": {}}
    for i in reversed(range(len(kinds))):
        lyr, attn = layer(params["layers"], i), kinds[i]
        if i in layers:
            _, pull = jax.vjp(
                lambda x, l: _block_jit(x, l, statics=statics, attn=attn),
                xs.pop(), lyr)
            dx, grads["layers"][i] = pull(dx)
        else:
            _, pull = jax.vjp(
                lambda x: _block_jit(x, lyr, statics=statics, attn=attn),
                xs.pop())
            dx, = pull(dx)
        del pull
    grads["embed"] = jnp.zeros_like(params["embed"]).at[tokens].add(dx)
    return ce, grads
