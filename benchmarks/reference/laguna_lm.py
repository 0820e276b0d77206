"""Plain reference of Laguna-S-2.1's decoder
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json), the
model ``lm_train_kinds`` trains for the configuration ``laguna-s-2.1-l5-e16``.
d = ``hidden_size``, D = ``head_dim``, KV = ``num_key_value_heads``:

    kind(l) = layer_types[l]          F = full_attention, S = sliding_attention
    H(l)    = num_attention_heads_per_layer[l];  group = H / KV
    h  = RMSNorm(x; g_attn)
    q  = h Wq [T, H, D]    k = h Wk [T, KV, D]    v = h Wv [T, KV, D]
    F: the first D * partial_rotary_factor dims of every head rotated with
       YaRN inverse frequencies (HF _compute_yarn_parameters), cos and sin
       times attention_factor; the other dims pass
    S: all D dims rotated, plain frequencies
    query head j reads K/V head j // group
    F: key s visible to query t  iff  s <= t
    S: key s visible to query t  iff  t - sliding_window < s <= t
    o  = softmax(q k^T / sqrt(D) + mask) v                  [T, H, D]
    g  = sigmoid(h Wg)                                      [T, H]
    x  = x + (g[..., None] * o).reshape(T, H * D) Wo
    h  = RMSNorm(x; g_mlp)
    dense layer:   x = x + W2 (silu(W1 h) * W3 h)
    sparse layer:  p = softmax(h Wr) over all experts, float32
                   (p_1..p_k, e_1..e_k) = top_k(p);  w_j = c * p_j / sum_i p_i
                   x = x + SE(h) + sum_{j : e_j held here} w_j E[e_j](h)
    loss = mean CE of RMSNorm(x; g_out) W_head

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense [T, T] scores and masks, a
Python loop over the layers and, for the routed FFN, a loop over the experts
held (a ``lax.scan`` over the expert axis of the weights) in which every
expert computes every token and a 0/1 mask times the route's weight keeps
what was routed: no kernel, no sort, no grouped matmul.  **The share.**  The
router has every expert's column; ``w1``/``w3``/``w2`` hold the experts
``experts_first .. experts_first + count`` (count read off the weights), and
what the other experts would add is left out, here as in the program; that
partial result goes on to the next layer (model-configs guide, section 4).
With all experts held this is the uncut layer (``tests/test_laguna.py``'s
share test adds the shares' routed parts up to it).

It reads the program's parameter tree (``embed``, ``out_norm``, ``head`` and
``layers``: a list of layers, leaves stacked ``[L, ...]``, or ``{"lead",
"period", "trail"}`` as ``multiverso_tpu.models.transformer.group_layers``
holds layers of different kinds) and the program's rotary layout: the
rotated dims are split in halves (x1 | x2), frequency i of ``half`` is
``theta ** (-i / half)``, positions count from 0.  ``model`` is the
configuration's ``model`` group.

Where the config is silent (``assumed`` in the configuration's file): softmax
router scores, the gate a sigmoid of a linear map of the layer's normed input,
the shared expert ungated, no QK-norm, the window holding ``sliding_window``
keys the query's own included, no auxiliary loss.

**The router flips, and here they weigh.**  Top-10 of 256 probabilities from
an untrained router lie close together, and where a token's 10th and 11th are
closer than the system's bfloat16 hidden state moves them the system routes
it elsewhere than this reference does; both are right.  OLMoE's weights are
the raw probabilities (about 1/64 each), so a swapped route hardly shows
(``olmoe_lm.py``).  Laguna renormalises the ten and scales them by 2.5: every
route weighs about 0.25, a swapped one changes that token's residual stream
by a quarter of an expert's output, and with it every gradient downstream.
Measured off the chip in bfloat16 at width 512 (PR 30): with the routed
experts' weight scaled to 0.001 the distances below halve (8-15% to 4-6%);
with all experts held, so that every swap shows, they reach 22-40%.  Handing
the reference the system's routes would hide it; ``routing_dtype`` (the
router's matmul and its input in bfloat16) is there to show that the
router's own arithmetic is not the cause: the distances do not move (routed
leaves 17.1-21.5% for 19.7-21.1%, two seeds).

Tolerances (used by ``benchmarks/runners/lm_train_kinds.py``; measured on the
chip in PR 30 at the published widths, 5 layers, one sequence of 1024 Zipf
tokens, 24 sampled leaves of layers 0, 2 and 4 with embedding rows and the
final norm gain; ``PERF.md`` section 6):

- ``LOSS_ATOL``: as ``dense_lm.py``.  Measured |difference| 1.1e-4 to 2.7e-3
  over 15 seeds at a loss of 9.89-9.91.
- ``GRAD_RTOL``, for every sampled leaf outside the routed experts' path:
  relative L2 distance between (old - new) / lr of the leaf and this file's
  gradient.  Over 15 seeds the worst leaf reads 8.6-11.2% (the full routed
  layer's ``wq`` / ``wk`` tiles), layer 0's leaves 5.4-7.2%, the final norm
  gain 3.1-3.9%.  (Not the update's rounding: a check step at learning rate
  1.0, where (old - new) is the gradient to within a rounding of the
  parameter, read the same to 0.3 points on every leaf but the norm gains.)
  The bound is 18%, 1.6 times the worst.  What it refuses, same samples:
  **the band switched to full causal** (``sliding_window`` 10**6 in this
  reference): the sliding layer's own ``wq`` / ``wk`` 28.4-31.9% for 6.3-8.5%,
  every leaf of layer 0 13.0-15.1%, 4 seeds, the worst 1.68 times the
  bound and more, while the loss moves by 1.7e-3 to 6.8e-3 and would pass;
  **this reference with its weights in the precision below** (rounded
  through float8 e4m3): every leaf above 24.7%, the worst 72-75%, 2 seeds;
  the gate left out: every leaf above 75%.
- ``GRAD_RTOL_ROUTED``, for the routers and the held experts' ``w2`` tiles
  (all 16 experts, 256 x 256 each), which take the swapped routes directly (a
  held expert sees about 40 of the sample's tokens, so one swapped route is a
  fortieth of its gradient): over 15 seeds the worst of them reads 16.2-22.3% (nine of those seeds:
  routers 12.0-21.1%, ``w2`` tiles 11.8-17.6%).  The bound is 35%, 1.57
  times the worst; float8
  weights read 62-76% there.  A full-causal band reads 23.6-32.3% on these
  leaves and is caught by ``GRAD_RTOL``, not here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["loss", "loss_and_grads", "layer", "inverse_frequencies",
           "LOSS_ATOL", "GRAD_RTOL", "GRAD_RTOL_ROUTED"]

LOSS_ATOL = 1e-2
GRAD_RTOL = 0.18
GRAD_RTOL_ROUTED = 0.35

FULL, SLIDING = "full_attention", "sliding_attention"


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


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def inverse_frequencies(recipe: dict, rotated: int) -> np.ndarray:
    """``[rotated / 2]`` inverse frequencies of a rotary recipe (the
    configuration's ``rope_full`` / ``rope_sliding``), in float64.  With
    ``yarn_factor``: HF ``_compute_yarn_parameters`` on ``dim = rotated``."""
    theta = float(recipe.get("theta", 10000.0))
    plain = 1.0 / theta ** (np.arange(0, rotated, 2, dtype=np.float64)
                            / rotated)
    factor = float(recipe.get("yarn_factor", 0.0))
    if not factor:
        return plain

    def correction_dim(rotations):
        return (rotated * math.log(recipe["original_max_seq"]
                                   / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(recipe.get("beta_fast", 32.0))), 0)
    high = min(math.ceil(correction_dim(recipe.get("beta_slow", 1.0))),
               rotated - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rotated // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    return (plain / factor) * (1.0 - extrapolation) + plain * extrapolation


def _rotary(x, recipe: dict):
    """x [B, T, H, D]."""
    T, D = x.shape[1], x.shape[-1]
    rotated = int(D * float(recipe.get("rotary_factor", 1.0)))
    half = rotated // 2
    freqs = jnp.asarray(inverse_frequencies(recipe, rotated), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    factor = float(recipe.get("attention_factor", 1.0))
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotated], x[..., rotated:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           axis=-1)


def _attention(x, lyr, st, attn: str, heads: int):
    """Pre-norm grouped-query attention with the per-head gate and the
    residual; x [B, T, dim]."""
    B, T, _ = x.shape
    D, kv = st["head_dim"], st["kv_heads"] or heads
    recipe = dict(st["rope_sliding"] if attn == SLIDING else st["rope_full"])
    h = _rms_norm(x, lyr["attn_norm"], st["eps"])
    q = _rotary((h @ lyr["wq"]).reshape(B, T, heads, D), recipe)
    k = _rotary((h @ lyr["wk"]).reshape(B, T, kv, D), recipe)
    v = (h @ lyr["wv"]).reshape(B, T, kv, D)
    group = heads // kv
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * D ** -0.5
    t = jnp.arange(T)
    visible = t[None, :] <= t[:, None]
    if attn == SLIDING:
        visible = visible & (t[None, :] > t[:, None] - st["window"])
    s = jnp.where(visible, s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    if st["gate"]:
        o = o * jax.nn.sigmoid(h @ lyr["wg"])[..., None]
    return x + o.reshape(B, T, heads * D) @ lyr["wo"]


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _ffn(x, lyr, st, ffn: str):
    B, T, dim = x.shape
    h = _rms_norm(x, lyr["mlp_norm"], st["eps"])
    if ffn == "dense":
        return x + _swiglu(h, lyr["w1"], lyr["w3"], lyr["w2"])
    h = h.reshape(B * T, dim)
    routed = h
    if st["routing_dtype"] is not None:      # the precision below, to show
        routed = h.astype(st["routing_dtype"])   # what the bound refuses
        logits = (routed @ lyr["router"].astype(st["routing_dtype"])
                  ).astype(jnp.float32)
    else:
        logits = routed @ lyr["router"]                          # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, st["top_k"])
    if st["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    top_p = top_p * st["routed_scale"]

    def one_expert(y, expert):
        e, w1, w3, w2 = expert
        weight = jnp.sum(jnp.where(top_idx == e, top_p, 0.0), axis=-1)  # [N]
        return y + weight[:, None] * _swiglu(h, w1, w3, w2), None

    held = lyr["w1"].shape[0]
    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (st["experts_first"] + jnp.arange(held), lyr["w1"], lyr["w3"],
         lyr["w2"]))
    if "shared_w1" in lyr:
        y = y + _swiglu(h, lyr["shared_w1"], lyr["shared_w3"],
                        lyr["shared_w2"])
    return x + y.reshape(B, T, dim)


def _block(x, lyr, statics, attn, heads, ffn):
    st = dict(statics)
    with jax.default_matmul_precision("highest"):
        return _ffn(_attention(x, lyr, st, attn, heads), lyr, st, ffn)


_block_jit = jax.jit(_block, static_argnames=("statics", "attn", "heads",
                                              "ffn"))


def _ce(x, out_norm, head, tokens, eps):
    """Mean next-token cross-entropy from the last hidden states."""
    with jax.default_matmul_precision("highest"):
        logits = _rms_norm(x, out_norm, eps) @ head              # [B, T, V]
    logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    picked = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                                 axis=-1)[..., 0]
    return jnp.mean(logz - picked)


_ce_jit = jax.jit(_ce, static_argnames=("eps",))


def _statics(model: dict, routing_dtype):
    """The layer function's static arguments (hashable), and the kinds."""
    L = model["n_layers"]

    def recipe(key):
        given = model.get(key) or {"theta": model.get("rope_theta", 10000.0)}
        return tuple(sorted(given.items()))

    statics = tuple(sorted(dict(
        head_dim=model.get("head_dim") or model["dim"] // model["n_heads"],
        kv_heads=model.get("n_kv_heads", 0),
        eps=float(model.get("norm_eps", 1e-5)),
        window=int(model.get("sliding_window", 0)),
        rope_full=recipe("rope_full"), rope_sliding=recipe("rope_sliding"),
        gate=bool(model.get("attn_gate", "")),
        top_k=model.get("top_k", 2),
        norm_topk_prob=bool(model.get("norm_topk_prob", True)),
        routed_scale=float(model.get("routed_scale", 1.0)),
        experts_first=int(model.get("experts_first", 0)),
        routing_dtype=routing_dtype).items()))
    ffn = "sparse" if model.get("num_experts", 0) else "dense"
    kinds = [dict(attn=(model.get("layer_types") or [FULL] * L)[i],
                  heads=(model.get("heads_per_layer")
                         or [model["n_heads"]] * L)[i],
                  ffn=(model.get("mlp_layer_types") or [ffn] * L)[i])
             for i in range(L)]
    return statics, kinds


def loss(params, tokens, model, routing_dtype=None):
    """Mean cross-entropy over every next-token position of ``tokens``
    [B, T]."""
    statics, kinds = _statics(model, routing_dtype)
    x = params["embed"][tokens]
    for i, kind in enumerate(kinds):
        x = _block_jit(x, layer(params["layers"], i), statics=statics, **kind)
    return _ce_jit(x, params["out_norm"], params["head"], tokens,
                   eps=float(model.get("norm_eps", 1e-5)))


def loss_and_grads(params, tokens, model, layers=(0,), routing_dtype=None):
    """``(loss, grads)`` with gradients for ``embed``, ``out_norm`` and
    every leaf of the layers named (``grads["layers"][i]``, a dict without
    the layer axis): the leaves the runner samples.  Reverse mode is
    ``jax.vjp`` of the plain functions above, chained over the layers by
    hand as in ``dense_lm.py``: each layer's forward runs again in the
    backward, one layer's activations are held at a time, and the other
    layers' weight gradients are not formed."""
    statics, kinds = _statics(model, routing_dtype)
    eps = float(model.get("norm_eps", 1e-5))
    xs = [params["embed"][tokens]]
    for i, kind in enumerate(kinds):
        xs.append(_block_jit(xs[-1], layer(params["layers"], i),
                             statics=statics, **kind))
    ce, pull = jax.vjp(
        lambda x, g: _ce_jit(x, g, params["head"], tokens, eps=eps),
        xs.pop(), params["out_norm"])
    dx, d_norm = pull(jnp.ones_like(ce))
    grads = {"out_norm": d_norm, "layers": {}}
    for i in reversed(range(len(kinds))):
        lyr, kind = layer(params["layers"], i), kinds[i]
        if i in layers:
            _, pull = jax.vjp(
                lambda x, l: _block_jit(x, l, statics=statics, **kind),
                xs.pop(), lyr)
            dx, grads["layers"][i] = pull(dx)
        else:
            _, pull = jax.vjp(
                lambda x: _block_jit(x, lyr, statics=statics, **kind),
                xs.pop())
            dx, = pull(dx)
        del pull
    grads["embed"] = jnp.zeros_like(params["embed"]).at[tokens].add(dx)
    return ce, grads
