"""Plain reference of Ling-3.0-flash's decoder
(https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json;
the language model: the vision tower is not the config's and is not run), the
model ``lm_train_linear`` trains for the configuration
``ling-3.0-flash-vl-l6``.  d = ``hidden_size``, H heads of D = ``head_dim``:

1. Linear layer (Kimi Delta Attention, arXiv:2510.26692, with this config's
   switches), h the sub-layer's RMS-normed input::

       q~, k~, v~ = h Wq, h Wk, h Wv                 [T, H D] each
       x_t <- SiLU(sum_j c[j] x~_{t-3+j})            causal depthwise conv, 4 taps, for q, k, v
       q, k <- x / sqrt(sum_D x^2 + 1e-6) a head;    q <- q D^-0.5
       a_t = lower * sigmoid(exp(A_log_h) (h Wf + dt_bias))      [T, H, D], lower = kda_lower_bound
       beta_t = sigmoid(h Wb)                                     [T, H]
       S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + beta_t k_t v_t^T      S_0 = 0, [D, D] a head
       o_t = S_t^T q_t
       y = (RMSNorm_D(o_t; g) * sigmoid(h Wg)_head) Wo

   The recurrence runs token by token (``lax.scan``; no chunk, no WY form),
   in segments of ``SEGMENT`` tokens whose inner states reverse mode rebuilds
   (``jax.checkpoint``), so that 2048 states of 32 x 128 x 128 are never held
   at once.

2. Latent layer (arXiv:2405.04434, decompressed, no query latent)::

       q = h Wq -> [T, H, d_n + d_r];   c_kv | k_r = h Wkv_a, k_r ONE head;   c_kv <- RMSNorm(c_kv)
       k_n | v = c_kv Wkv_b;   q_r, k_r rotated (theta, halves);   scale (d_n + d_r)^-0.5, causal softmax
       y = (softmax(s) v * sigmoid(h Wg)_head) Wo

3. FFN: ``dense`` SwiGLU, or ``sparse``: s = sigmoid(h Wr) over all E experts
   in float32; the E scores + bias lie in ``n_group`` groups of adjacent
   experts, a group's score is the sum of its two highest, the ``topk_group``
   best groups stay (explicit masks); the k experts are the top of s + b among
   the experts that stay; w = s at the chosen / their sum * ``routed_scale``;
   plus the shared SwiGLU.  After the step ``b_e += rate * sign(mean(load) -
   load_e)``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``; a Python loop over the experts
held (every expert computes every token and a mask times the route's weight
keeps what was routed).  **The share**, as ``laguna_lm.py`` and ``xing_lm.py``:
the router and the bias have every expert's column, ``w1``/``w3``/``w2`` hold
``experts_first ..`` (count read off the weights), what the others would add is
left out.  It reads the program's parameter tree (``layer``) and its rotary
layout (rotated dims split in halves), and nothing else of
``multiverso_tpu``: no line of ``ops/kda.py`` or ``models/``.

Reverse mode is ``jax.vjp`` chained over the layers by hand (each layer's
forward runs again in the backward and only the sampled layers' weight
gradients are formed), so that the published widths fit beside the program's
own copy.

Tolerances (used by ``benchmarks/runners/lm_train_linear.py``; measured on the
chip in PR 36 at the published widths; ``PERF.md`` section 6).  A wrong
program's reading is this file's own with one switch of ``_statics`` thrown,
put in the program's place (the runner's ``controls``).  Each wrong program
fails at least one bound, not each bound.

**The scan alone** (``scan_and_grads`` against ``ops/kda.py:kda`` and its
backward on the runner's ``scan_inputs``: one sequence of 2048 tokens, 32
heads of 128 x 128, q, k, v and the cotangent in bfloat16; six seeds on the
chip, the Mosaic forward and XLA's chunked backward):

- ``SCAN_RTOL`` 0.009, the output's relative L2 distance: the program reads
  0.00404 to 0.00409.  **A bfloat16 state** (the recurrence's state rounded
  to bfloat16 after every token) reads 0.0195 to 0.0198; the decay left out
  0.41 to 0.51.
- ``SCAN_GRAD_RTOL`` 0.011, the worst of the five gradients: the program
  reads 0.00494 to 0.00495 (``dk``).  **A bfloat16 state** reads 0.0279 to
  0.0285 (``da``); the decay left out exactly 1 (``da`` is then zero).
- **Not told apart, here or below: a bfloat16 decay** (``decay_dtype``:
  ``exp(a_t)`` rounded to bfloat16 before it multiplies the state): 0.0033
  to 0.0076 and 0.0047 to 0.0109 over the six seeds, on both sides of what
  the program's own bfloat16 operands cost.  No limit stands between the two
  with room, so that guarantee is unguarded (``PERF.md`` section 7).

**One train step** (6 layers, 1 x 2048 Zipf tokens, SGD 0.002, 47 sampled
leaves of the three kinds of layer with embedding rows and the final norm's
gain, 16 rows of logits; 22 seeds).  The controls' readings are three seeds'
(987654321, 2100000011, 41; the bfloat16 decay's the first's alone), through
the runner's ``compare``; the state's precision is the scan's bounds' to
hold, these stand nearer to it:

- ``LOSS_ATOL`` 1e-2: measured |difference| 0.00003 to 0.0044 at a loss of
  10.3.  A bfloat16 state reads 0.0010 to 0.0030 and passes here.
- ``LOGITS_RTOL`` 0.09, on the MEDIAN of the 16 rows' relative L2 distances
  (the forward pass alone, six layers of bfloat16 compute): 0.052 to 0.069.
  **A bfloat16 state** reads 0.111 to 0.115 (refused on all three seeds),
  the decay left out 0.94 to 1.00, a bfloat16 decay 0.056.  The worst row is
  one row's luck (0.062 to 0.287, a bfloat16 state 0.185 to 0.268) and is
  logged, not bounded.
- ``GRAD_RTOL`` 0.20, every sampled leaf outside the two classes below:
  relative L2 distance between (old - new) / lr of the leaf and this file's
  gradient.  A seed's worst leaf reads 0.127 to 0.170 (the first layers'
  convolution kernels, ``wq``, ``wb``, the head norm's gain: the leaves with
  the longest path to the loss; a seed's median leaf 0.118-0.150), the
  latent layer's 0.02-0.09.  **A bfloat16 state** reads 0.229 to 0.248
  (``conv_k``, ``wb``, ``wk`` of layer 0: what feeds the state longest;
  refused on all three seeds), the decay left out 1.35 to 1.41, a bfloat16
  decay 0.138.  What it refuses is a leaf's gradient that is wrong outright (a
  convolution tap or a gate left out: not run).
- ``GRAD_RTOL_ROUTED`` 0.80, the routers and the held experts' ``w2`` tiles,
  which take every swapped route directly (``xing_lm.py``, whose bound this
  is): 0.29 to 0.70, the latent layer's router the worst (0.46 to 0.70, mean
  0.57).  It has no reading from above that is its own: routing without the
  group limit reads 0.78 to 0.95 and is refused by ``KEPT_MISMATCH``, a
  bfloat16 state 0.75; the decay left out reads 1.43 to 1.46.
- ``GRAD_RTOL_DECAY`` 0.80, the decay's own leaves (``dt_bias``, ``wf``:
  0.11-0.21; ``A_log``, 32 numbers a layer whose gradients are sums of signed
  terms over a whole head: 0.11 to 0.48, a seed's worst always one of them).
  **The decay left out** (``decay=False``) reads exactly 1: the three leaves'
  gradients are then zero, whatever the machine.
- ``BIAS_MISMATCH`` 0.10: the share of the 5 x 512 biases after the step that
  differ from this file's by more than half the rule's rate: 0.018 to 0.030.
  The rule left out reads the share of experts whose load is not exactly
  the mean, about 0.97.
- ``KEPT_MISMATCH`` 0.05: the largest difference, a routed layer, between the
  program's count of tokens that kept the held experts' group
  (``TransformerTrainer.kept``) and this file's, over the
  tokens: 0.003 to 0.014 (router flips at a group's margin).  **The group
  masks left out** (``group_limit=False``): every token keeps every group,
  0.53 to 0.62.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["loss", "loss_and_grads", "layer", "scan_and_grads", "LOSS_ATOL",
           "LOGITS_RTOL", "GRAD_RTOL", "GRAD_RTOL_ROUTED", "GRAD_RTOL_DECAY",
           "BIAS_MISMATCH", "KEPT_MISMATCH", "SCAN_RTOL", "SCAN_GRAD_RTOL"]

LOSS_ATOL = 1e-2
LOGITS_RTOL = 0.09
GRAD_RTOL = 0.20
GRAD_RTOL_ROUTED = 0.80
GRAD_RTOL_DECAY = 0.80
BIAS_MISMATCH = 0.10
# The share of the check's tokens by which a routed layer's count of tokens
# that kept the held experts' group may differ from this file's:
KEPT_MISMATCH = 0.05
# The scan alone (``scan_and_grads`` against ``ops/kda.py:kda`` on the
# runner's ``scan_inputs``): relative L2 distance of the output, and of the
# worst of the five gradients.
SCAN_RTOL = 0.009
SCAN_GRAD_RTOL = 0.011
Q_BLOCK = 1024
SEGMENT = 64


def _kinds(model: dict):
    L = model["n_layers"]
    ffn = "sparse" if model.get("num_experts", 0) else "dense"
    return list(zip(model.get("layer_types") or ["full_attention"] * L,
                    model.get("mlp_layer_types") or [ffn] * L))


def layer(layers, i: int):
    """Layer ``i``'s own leaves out of the program's ``layers`` tree, read as
    it is found: a list, one dict stacked over the layers, or ``{"lead",
    "period", "trail"}`` whose period entries are slots stacked ``[periods,
    ...]`` or runs of consecutive slots alike stacked ``[periods, count,
    ...]`` (the rank of ``attn_norm``, a vector a layer, says which)."""
    if isinstance(layers, (list, tuple)):
        return layers[i]
    if "period" not in layers:                  # every layer alike, stacked
        return jax.tree_util.tree_map(lambda v: v[i], layers)
    lead, period, trail = layers["lead"], layers["period"], layers["trail"]
    if i < len(lead):
        return lead[i]
    counts = [e["attn_norm"].shape[1] if e["attn_norm"].ndim == 3 else 0
              for e in period]                  # 0: a slot of its own
    p, repeats = sum(max(c, 1) for c in counts), period[0]["attn_norm"].shape[0]
    j = i - len(lead)
    if j >= p * repeats:
        return trail[j - p * repeats]
    slot = j % p
    for entry, count in zip(period, counts):
        if slot < max(count, 1):
            at = (j // p, slot) if count else (j // p,)
            return jax.tree_util.tree_map(lambda v: v[at], entry)
        slot -= max(count, 1)
    raise IndexError(i)


def _rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def _rotary(x, theta: float):
    """x [B, T, H, D], every dim rotated (halves x1 | x2)."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = jnp.asarray(1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64)
                                        / D), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _conv_silu(x, c):
    """Causal depthwise convolution as shifted adds: tap ``j`` of ``c [taps,
    width]`` reads the token ``taps - 1 - j`` back; then SiLU."""
    taps = c.shape[0]
    y = x * c[taps - 1]
    for back in range(1, taps):
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :-back]], axis=1)
        y = y + shifted * c[taps - 1 - back]
    return jax.nn.silu(y)


def _recurrence(q, k, v, a, beta, st):
    """Item 1's recurrence: q, k, a [B, T, H, D], v [B, T, H, D], beta [B,
    T, H] → o [B, T, H, D]."""
    B, T, H, D = q.shape
    if not st["decay"]:
        a = jnp.zeros_like(a)

    def kept_in(dtype):
        # An explicit rounding: XLA's TPU compiler removes a pair of converts
        # (f32 -> bf16 -> f32) as excess precision it is allowed to keep.
        if dtype is None:
            return lambda s: s
        info = jnp.finfo(dtype)
        return lambda s: jax.lax.reduce_precision(s, info.nexp, info.nmant)

    keep, decay = kept_in(st["state_dtype"]), kept_in(st["decay_dtype"])

    def token(S, x):
        # Elementwise over the [D, D] state: no matmul unit, no chunk.
        q, k, v, a, b = x                                   # [B, H, D], b [B, H]
        S = S * decay(jnp.exp(a))[..., None]
        u = b[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
        S = keep(S + k[..., None] * u[..., None, :])
        return S, jnp.sum(S * q[..., None], axis=-2)

    @jax.checkpoint
    def segment(S, xs):
        return jax.lax.scan(token, S, xs)

    pad = -T % SEGMENT
    xs = [jnp.moveaxis(jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),)
                               * (x.ndim - 2)), 1, 0)
          for x in (q, k, v, a, beta)]          # padded tokens: beta 0, a 0
    xs = tuple(x.reshape(-1, SEGMENT, *x.shape[1:]) for x in xs)
    _, o = jax.lax.scan(segment, jnp.zeros((B, H, D, D), jnp.float32), xs)
    return jnp.moveaxis(o.reshape(-1, B, H, D)[:T], 0, 1)


@functools.partial(jax.jit, static_argnames=("state_dtype", "decay_dtype",
                                              "decay"))
def scan_and_grads(q, k, v, a, beta, d_o, state_dtype=None, decay_dtype=None,
                   decay=True):
    """Item 1's recurrence alone, from a zero state: ``(o, (dq, dk, dv, da,
    dbeta))``, the gradients those of ``sum(o * d_o)``.  The switches are
    ``_statics``'s."""
    st = {"state_dtype": state_dtype, "decay_dtype": decay_dtype,
          "decay": decay}
    o, pull = jax.vjp(lambda *xs: _recurrence(*xs, st), q, k, v, a, beta)
    return o, pull(d_o)


def _head_gate(o, h, lyr):
    return o * jax.nn.sigmoid(h @ lyr["wg"])[..., None] if "wg" in lyr else o


def _linear_attention(h, lyr, st):
    B, T, _ = h.shape
    H = lyr["wb"].shape[1]
    D = lyr["wq"].shape[1] // H
    heads = lambda x: x.reshape(B, T, H, D)
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    q = unit(heads(_conv_silu(h @ lyr["wq"], lyr["conv_q"]))) * D ** -0.5
    k = unit(heads(_conv_silu(h @ lyr["wk"], lyr["conv_k"])))
    v = heads(_conv_silu(h @ lyr["wv"], lyr["conv_v"]))
    a = st["lower"] * jax.nn.sigmoid(
        jnp.exp(lyr["A_log"])[:, None] * heads(h @ lyr["wf"]
                                               + lyr["dt_bias"]))
    o = _recurrence(q, k, v, a, jax.nn.sigmoid(h @ lyr["wb"]), st)
    o = _head_gate(_rms_norm(o, lyr["o_norm"], st["eps"]), h, lyr)
    return o.reshape(B, T, H * D) @ lyr["wo"]


def _latent_attention(h, lyr, st):
    B, T, _ = h.shape
    H, dn, dr, dv = st["heads"], st["dn"], st["dr"], st["dv"]
    q = (h @ lyr["wq"]).reshape(B, T, H, dn + dr)
    kv_a = h @ lyr["wkv_a"]
    rank = kv_a.shape[-1] - dr
    c_kv = _rms_norm(kv_a[..., :rank], lyr["kv_a_norm"], st["eps"])
    kv = (c_kv @ lyr["wkv_b"]).reshape(B, T, H, dn + dv)
    q_n, q_r = q[..., :dn], _rotary(q[..., dn:], st["theta"])
    k_n, v = kv[..., :dn], kv[..., dn:]
    k_r = _rotary(kv_a[..., rank:][:, :, None, :], st["theta"])[:, :, 0]
    scale = (dn + dr) ** -0.5
    t = jnp.arange(T)
    out = []
    for lo in range(0, T, Q_BLOCK):              # a block of query rows
        rows = slice(lo, min(lo + Q_BLOCK, T))
        s = (jnp.einsum("bthd,bshd->bhts", q_n[:, rows], k_n)
             + jnp.einsum("bthd,bsd->bhts", q_r[:, rows], k_r))
        s = jnp.where(t[None, :] <= t[rows, None], s * scale, -jnp.inf)
        out.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v))
    o = _head_gate(jnp.concatenate(out, axis=1), h, lyr)
    return o.reshape(B, T, H * dv) @ lyr["wo"]


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _routed(h, lyr, st):
    """Item 3's sparse FFN of h [B, T, d]: ``(output, load [E], tokens that
    kept the group of the first expert held)``."""
    B, T, dim = h.shape
    h = h.reshape(B * T, dim)
    scores = jax.nn.sigmoid(h @ lyr["router"])                   # [N, E]
    N, E = scores.shape
    chosen = scores + lyr["router_bias"]
    n_group, topk_group = st["groups"]
    kept = jnp.ones((N, n_group), bool)
    if n_group > 1 and st["group_limit"]:
        grouped = chosen.reshape(N, n_group, E // n_group)
        two = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)       # [N, groups]
        # a group stays when fewer than topk_group groups score higher
        # (ties to the lower index, as a stable top-k breaks them)
        g = jnp.arange(n_group)
        higher = (two[:, None, :] > two[:, :, None]) | (
            (two[:, None, :] == two[:, :, None]) & (g[None, :] < g[:, None]))
        kept = jnp.sum(higher, axis=-1) < topk_group
        chosen = jnp.where(jnp.repeat(kept, E // n_group, axis=1), chosen,
                           -jnp.inf)
    _, top_idx = jax.lax.top_k(chosen, st["top_k"])
    top_s = jnp.take_along_axis(scores, top_idx, axis=-1)
    if st["norm_topk_prob"]:
        top_s = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    top_s = top_s * st["routed_scale"]
    y = jnp.zeros_like(h)
    for e in range(lyr["w1"].shape[0]):          # the experts held here
        weight = jnp.sum(jnp.where(top_idx == st["experts_first"] + e,
                                   top_s, 0.0), axis=-1)
        y = y + weight[:, None] * _swiglu(h, lyr["w1"][e], lyr["w3"][e],
                                          lyr["w2"][e])
    if "shared_w1" in lyr:
        y = y + _swiglu(h, lyr["shared_w1"], lyr["shared_w3"],
                        lyr["shared_w2"])
    load = jnp.sum(top_idx[..., None] == jnp.arange(E), axis=(0, 1))
    mine = st["experts_first"] // (E // n_group)
    return (y.reshape(B, T, dim), load.astype(jnp.int32),
            jnp.sum(kept[:, mine]).astype(jnp.int32))


def _block(x, lyr, statics, kind):
    """One decoder layer: ``(x', load [E] or None, kept or None)``."""
    st = dict(statics)
    attn, ffn = kind
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, lyr["attn_norm"], st["eps"])
        x = x + (_linear_attention(h, lyr, st) if attn == "linear_attention"
                 else _latent_attention(h, lyr, st))
        h = _rms_norm(x, lyr["mlp_norm"], st["eps"])
        if ffn == "dense":
            return x + _swiglu(h, lyr["w1"], lyr["w3"], lyr["w2"]), None, None
        out, load, kept = _routed(h, lyr, st)
        return x + out, load, kept


_block_jit = jax.jit(_block, static_argnames=("statics", "kind"))


def _ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def _head(x, out_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, out_norm, eps) @ head


def _tail(x, out_norm, head, tokens, eps):
    return _ce(_head(x, out_norm, head, eps)[:, :-1], tokens[:, 1:])


_tail_jit = jax.jit(_tail, static_argnames=("eps",))
_head_jit = jax.jit(_head, static_argnames=("eps",))


def _statics(model: dict, state_dtype=None, decay_dtype=None,
             group_limit=True, decay=True):
    """The layer function's static arguments (hashable).  The four switches
    exist to show what the runner's bounds refuse: the recurrence's state
    (rounded after every token) or its decay ``exp(a_t)`` kept in a narrower
    type, the group masks left out, the decay left out (``exp(a_t) = 1``)."""
    rope = model.get("rope_latent") or {}
    return tuple(sorted(dict(
        heads=model["n_heads"], dn=model["qk_nope_dim"],
        dr=model["qk_rope_dim"], dv=model["v_head_dim"],
        theta=float(rope.get("theta", model.get("rope_theta", 10000.0))),
        eps=float(model.get("norm_eps", 1e-5)),
        lower=float(model.get("kda_lower_bound", -5.0)),
        top_k=model.get("top_k", 2),
        groups=(int(model.get("n_group", 1)), int(model.get("topk_group",
                                                            1))),
        norm_topk_prob=bool(model.get("norm_topk_prob", True)),
        routed_scale=float(model.get("routed_scale", 1.0)),
        experts_first=int(model.get("experts_first", 0)),
        state_dtype=state_dtype, decay_dtype=decay_dtype,
        group_limit=bool(group_limit), decay=bool(decay)).items()))


def _hidden(params, tokens, model, statics):
    """Every layer's input, the last layer's output last, and ``{layer:
    (load, kept)}`` of the routed ones."""
    xs, counted = [params["embed"][tokens]], {}
    for i, kind in enumerate(_kinds(model)):
        x, load, kept = _block_jit(xs[-1], layer(params["layers"], i),
                                   statics=statics, kind=kind)
        xs.append(x)
        if load is not None:
            counted[i] = (load, kept)
    return xs, counted


def loss(params, tokens, model, **switches):
    """The training loss of ``tokens`` [B, T]."""
    statics = _statics(model, **switches)
    x = _hidden(params, tokens, model, statics)[0][-1]
    return _tail_jit(x, params["out_norm"], params["head"], tokens,
                     eps=dict(statics)["eps"])


def loss_and_grads(params, tokens, model, layers=(0,), positions=None,
                   **switches):
    """``(loss, grads, bias_after, kept, logits)``: gradients for ``embed``,
    ``out_norm`` and every leaf of the layers named (``grads["layers"][i]``,
    a dict without the layer axes); ``bias_after`` the correction bias of
    every routed layer after the rule and ``kept`` its tokens that kept the
    held experts' group (``{layer index: .}``); the logits at ``positions``
    (``None``: none)."""
    statics = _statics(model, **switches)
    eps = dict(statics)["eps"]
    kinds = _kinds(model)
    rate = float(model.get("router_bias_rate", 0.001))
    xs, counted = _hidden(params, tokens, model, statics)
    rows = None if positions is None else _head_jit(
        xs[-1][:, positions], params["out_norm"], params["head"], eps=eps)
    total, pull = jax.vjp(
        lambda x, g: _tail_jit(x, g, params["head"], tokens, eps=eps),
        xs.pop(), params["out_norm"])
    dx, d_norm = pull(jnp.ones_like(total))
    grads = {"out_norm": d_norm, "layers": {}}
    for i in reversed(range(len(kinds))):
        lyr, kind = layer(params["layers"], i), kinds[i]
        if i in layers:
            _, pull = jax.vjp(
                lambda x, l: _block_jit(x, l, statics=statics, kind=kind)[0],
                xs.pop(), lyr)
            dx, grads["layers"][i] = pull(dx)
        else:
            _, pull = jax.vjp(
                lambda x: _block_jit(x, lyr, statics=statics, kind=kind)[0],
                xs.pop())
            dx, = pull(dx)
        del pull
    grads["embed"] = jnp.zeros_like(params["embed"]).at[tokens].add(dx)

    def after(i, load):
        load = load.astype(jnp.float32)
        return (layer(params["layers"], i)["router_bias"]
                + rate * jnp.sign(jnp.mean(load) - load))

    return (total, grads, {i: after(i, load) for i, (load, _) in
                           counted.items()},
            {i: kept for i, (_, kept) in counted.items()}, rows)
