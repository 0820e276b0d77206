"""Plain reference of Xing4.0-29B-A4B's decoder
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json),
the model ``lm_train_latent`` trains for the configuration
``xing4.0-29b-a4b-e8``.  d = ``hidden_size``, H heads, d_n / d_r / d_v =
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``, n = ``hc_mult``:

1. Latent attention (arXiv:2405.04434, decompressed: no weight absorption),
   h the sub-layer's normed input::

       c_q = RMSNorm(h Wq_a)            q = c_q Wq_b -> [T, H, d_n + d_r] = q_n | q_r
       c_kv | k_r = h Wkv_a             k_r ONE head [T, d_r];  c_kv = RMSNorm(c_kv)
       k_n | v = c_kv Wkv_b             -> [T, H, d_n + d_v]
       q_r, k_r rotated (YaRN inverse frequencies over the d_r dims, cos/sin x attention_factor)
       s[t,u,j] = (q_n[t,j] . k_n[u,j] + q_r[t,j] . k_r[u]) * (d_n + d_r)^-0.5 * m^2,  u <= t
       out = softmax(s) v Wo            m = ``attn_mscale`` (0.1 mscale_all_dim ln(factor) + 1)

2. Hyper-connections (arXiv:2512.24880 over arXiv:2409.19606), one set a
   sub-layer F, streams X [T, n, d]::

       x' = vec(X) / rms(vec(X))                      a = x' Phi   [T, 2n + n*n]
       H_pre  = sigmoid(alpha_0 a_pre + b_pre)        [T, n]
       H_post = 2 sigmoid(alpha_1 a_post + b_post)    [T, n]
       M = exp(clip(alpha_2 a_res + b_res, lo, hi))   [T, n, n]; 20 times: rows /= (row sum + eps),
                                                      columns /= (column sum + eps)   -> H_res
       u = sum_i H_pre[i] X[i];   X'[i] = sum_j H_res[i,j] X[j] + H_post[i] F(RMSNorm(u; g))

   The embedding row is copied into the n streams; their mean goes on to
   the final norm.

3. FFN: layer kinds ``dense`` (SwiGLU) or ``sparse``: s = sigmoid(h Wr) over
   all experts; the k experts are the top of s + b (b the correction bias);
   w = s at the chosen / their sum * ``routed_scale``; plus the shared
   SwiGLU.  After the step ``b_e += rate * sign(mean(load) - load_e)``,
   ``load`` the routes every expert was sent (``bias_after``).

4. Multi-token prediction (arXiv:2412.19437): ``g = [RMSNorm(x) |
   RMSNorm(E[tok_{t+1}])] Wm`` copied into n streams, one layer of its own
   (items 1-3), streams' mean, its own final norm, the shared head;
   ``loss = CE(token t+1) + mtp_loss_coef * CE(token t+2)``.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense masked scores a block of
query rows at a time (``Q_BLOCK``), a Python loop over the experts held (every
expert computes every token and a 0/1 mask times the route's weight keeps
what was routed) and over Sinkhorn's iterations; no kernel, no scan, no
sort.  **The share**, as ``laguna_lm.py``: the router and the bias have every
expert's column, ``w1``/``w3``/``w2`` hold ``experts_first ..`` (count read
off the weights), what the others would add is left out.  It reads the
program's parameter tree and its rotary layout (rotated dims split in
halves), and nothing else of ``multiverso_tpu``.

Departures from the papers, here as in the program: (a) the bias rule's
``load`` is this chip's tokens, not summed over data-parallel chips; (b) the
prediction module runs over all T positions, the last one paired with a zero
row in place of the embedding of a token that does not exist; that position
takes no loss and, under the causal mask, moves no other, but its k routes
are in the module layer's load; (c) reverse mode is ``jax.vjp`` chained over
the layers by hand (each layer's forward runs again in the backward and only
the sampled layers' weight gradients are formed), so that the published
widths fit beside the program's own copy.

Tolerances (used by ``benchmarks/runners/lm_train_latent.py``; measured on
the chip in PR 32 at the published widths, 1 + 6 layers + the module, one
sequence of 1024 Zipf tokens, SGD 0.002, 53 sampled leaves of the dense
layer, a routed layer and the module with embedding rows and norm gains,
over seven seeds; what a wrong program reads is one seed's, from the
switches of ``_statics``).  Each bound lies between what the program reads
and what a wrong program or a lower precision reads; each of those fails at
least one bound, not each bound:

- ``LOSS_ATOL`` 2e-2: measured |difference| 0.00002 to 0.0065 (mean 0.003)
  at a loss of 13.4 (10.0 + 0.3 x 10.2; bfloat16 logits twice, and the
  routes below).  **The second loss left out** moves it by 3.04.  The
  rotated part left out (0.0185) and float8 weights (0.0136) pass here and
  fail the next.
- ``GRAD_RTOL`` 0.40, for every sampled leaf outside the routed experts'
  path and outside the gates' scalars: relative L2 distance between (old -
  new) / lr of the leaf and this file's gradient.  A seed's worst leaf reads
  0.209 to 0.293 (a latent norm's gain or head 0's ``wq_b`` tile in the
  routed layer or the module), the dense layer's leaves 0.13-0.24, the
  median leaf 0.11.  **This reference with its weights in the precision
  below** (every matrix rounded through float8 e4m3) reads above 0.66 on
  half the leaves (its ``phi`` rounds to zero); **the rotated score part
  left out** 1.49 on the latent norms and 0.87 on the median leaf;
  **bfloat16 masters** (the leaves rounded before and after the step) 38.
- ``GRAD_RTOL_ROUTED`` 0.80, for the routers and the held experts' ``w2``
  tiles, which take every swapped route directly (``laguna_lm.py``: "the
  router flips"; here a route weighs about 0.5, top-4 normalised x 2, twice
  Laguna's, through seven routed blocks in a row): routers read 0.389 to
  0.613, ``w2`` tiles 0.291 to 0.407.  Float8 weights read 1.13, the rotated
  part left out 1.58, bfloat16 masters 57.
- ``BIAS_MISMATCH`` 0.10: the share of the 7 x 64 biases after the step that
  differ from this file's by more than half the rule's rate.  Reads 0.007 to
  0.036 (a router flip near an expert's mean load moves its sign); **the
  rule left out** or turned around reads the share of experts whose load is
  not exactly the mean, about 0.97; the rotated part left out 0.16.
- **Sinkhorn left out** (``sinkhorn_iters=0``) is not a matter of bounds:
  ``exp(8)`` on the diagonal of every sub-layer multiplies the streams by
  2,981 a time and the loss is not finite.
- **Not told apart on the chip: bfloat16 gates and Sinkhorn**
  (``gates_dtype``).  At the initial values the gates barely move with the
  streams, so the only leaves that feel their precision are the gates' own
  ``alpha`` and ``b``, and those gradients are sums of thousands of signed
  terms that nearly cancel: against this file they read 0.003 to 7.9 over
  the seven seeds in float32, and 0.40 against 0.18 (an ``alpha``), 0.27
  against 0.21 (a ``b``) with bfloat16 gates on the one seed tried: inside
  their own scatter.  The runner logs them, holds them finite and keeps
  them out of ``GRAD_RTOL`` (``lm_train_latent.gate_scalar``); ``phi``
  (0.09-0.23) stays in.  On the CPU, in float32 and with the gates moved off
  their resting values, bfloat16 gates fail the comparison by 1e-4 of loss
  where the program agrees to 1e-5 (``tests/test_xing.py``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["loss", "loss_and_grads", "layer", "inverse_frequencies",
           "sinkhorn", "LOSS_ATOL", "GRAD_RTOL", "GRAD_RTOL_ROUTED",
           "BIAS_MISMATCH"]

LOSS_ATOL = 2e-2
GRAD_RTOL = 0.40
GRAD_RTOL_ROUTED = 0.80
# The rule moves every bias by exactly +-rate or 0.  The share of experts
# (over the routed layers and the module) whose bias after the step may be
# another than this file's:
BIAS_MISMATCH = 0.10
Q_BLOCK = 1024


def layer(layers, i: int):
    """Layer ``i``'s own leaves out of the program's ``layers`` tree."""
    if isinstance(layers, (list, tuple)):
        return layers[i]
    if "period" not in layers:
        return jax.tree_util.tree_map(lambda v: v[i], layers)
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
    """``[rotated / 2]`` inverse frequencies of a rotary recipe, float64;
    with ``yarn_factor``: HF ``_compute_yarn_parameters`` on ``dim =
    rotated``."""
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
    return (plain / factor) * ramp + plain * (1.0 - ramp)


def _rotary(x, recipe: dict):
    """x [B, T, H, D], every dim rotated (halves x1 | x2)."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = jnp.asarray(inverse_frequencies(recipe, D), jnp.float32)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    factor = float(recipe.get("attention_factor", 1.0))
    cos = (jnp.cos(ang) * factor)[None, :, None, :]
    sin = (jnp.sin(ang) * factor)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _latent_attention(h, lyr, st):
    """Item 1 without the residual; h [B, T, d] normed."""
    B, T, _ = h.shape
    H, dn, dr, dv = st["heads"], st["dn"], st["dr"], st["dv"]
    recipe = dict(st["rope"])
    c_q = _rms_norm(h @ lyr["wq_a"], lyr["q_a_norm"], st["eps"])
    q = (c_q @ lyr["wq_b"]).reshape(B, T, H, dn + dr)
    kv_a = h @ lyr["wkv_a"]
    rank = kv_a.shape[-1] - dr
    c_kv = _rms_norm(kv_a[..., :rank], lyr["kv_a_norm"], st["eps"])
    kv = (c_kv @ lyr["wkv_b"]).reshape(B, T, H, dn + dv)
    q_n, q_r = q[..., :dn], q[..., dn:]
    k_n, v = kv[..., :dn], kv[..., dn:]
    if st["rotated_part"]:
        q_r = _rotary(q_r, recipe)
        k_r = _rotary(kv_a[..., rank:][:, :, None, :], recipe)[:, :, 0]
    scale = (dn + dr) ** -0.5 * st["mscale"] ** 2
    t = jnp.arange(T)
    out = []
    for lo in range(0, T, Q_BLOCK):              # a block of query rows
        rows = slice(lo, min(lo + Q_BLOCK, T))
        s = jnp.einsum("bthd,bshd->bhts", q_n[:, rows], k_n)
        if st["rotated_part"]:
            s = s + jnp.einsum("bthd,bsd->bhts", q_r[:, rows], k_r)
        s = jnp.where(t[None, :] <= t[rows, None], s * scale, -jnp.inf)
        out.append(jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1), v))
    o = jnp.concatenate(out, axis=1)
    return o.reshape(B, T, H * dv) @ lyr["wo"]


def sinkhorn(m, iters: int, eps: float):
    """``iters`` times: rows of ``m [..., n, n]`` over (their sum + eps),
    then columns over (theirs + eps)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def _gates(X, hc, st):
    """Item 2's three maps for the streams X [B, T, n, d]."""
    B, T, n, d = X.shape
    dt = st["gates_dtype"] or jnp.float32        # the precision below: to
    flat = X.reshape(B, T, n * d)                # show what a bound refuses
    normed = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                  + st["eps"])
    a = (normed @ hc["phi"]).astype(dt)
    alpha, b = hc["alpha"].astype(dt), hc["b"].astype(dt)
    pre = jax.nn.sigmoid(alpha[0] * a[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * a[..., 2 * n:] + b[2 * n:],
                         st["clamp"][0], st["clamp"][1])).reshape(B, T, n, n)
    if st["sinkhorn"]:
        m = sinkhorn(m, st["sinkhorn"], jnp.asarray(st["hc_eps"], dt))
    return (pre.astype(jnp.float32), post.astype(jnp.float32),
            m.astype(jnp.float32))


def _hyper(X, hc, st, f):
    """One sub-layer ``f`` (normed input -> output) through its
    hyper-connections; without streams (X [B, T, d]) the residual."""
    if X.ndim == 3:
        return X + f(X)
    pre, post, res = _gates(X, hc, st)
    u = jnp.einsum("btn,btnd->btd", pre, X)
    return (jnp.einsum("btij,btjd->btid", res, X)
            + post[..., None] * f(u)[:, :, None, :])


def _swiglu(h, w1, w3, w2):
    return (jax.nn.silu(h @ w1) * (h @ w3)) @ w2


def _routed(h, lyr, st):
    """Item 3's sparse FFN of h [B, T, d]: ``(output, load [E])``."""
    B, T, dim = h.shape
    h = h.reshape(B * T, dim)
    scores = jax.nn.sigmoid(h @ lyr["router"])                   # [N, E]
    E = scores.shape[-1]
    chosen = scores + (lyr["router_bias"] if st["bias"] else 0.0)
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
    return y.reshape(B, T, dim), load.astype(jnp.int32)


def _block(X, lyr, statics, ffn):
    """One decoder layer on the streams: ``(X', load [E] or None)``."""
    st = dict(statics)
    with jax.default_matmul_precision("highest"):
        X = _hyper(X, lyr.get("hc_attn"), st, lambda u: _latent_attention(
            _rms_norm(u, lyr["attn_norm"], st["eps"]), lyr, st))
        load = []

        def feed(u):
            h = _rms_norm(u, lyr["mlp_norm"], st["eps"])
            if ffn == "dense":
                return _swiglu(h, lyr["w1"], lyr["w3"], lyr["w2"])
            out, counted = _routed(h, lyr, st)
            load.append(counted)
            return out

        X = _hyper(X, lyr.get("hc_mlp"), st, feed)
    return X, (load[0] if load else None)


_block_jit = jax.jit(_block, static_argnames=("statics", "ffn"))


def _ce(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def _collapse(X):
    return X if X.ndim == 3 else jnp.mean(X, axis=2)


def _tail(X, out_norm, embed, mtp, head, tokens, statics, ffn):
    """From the last layer's streams to ``(loss, the module's load)``: the
    next-token cross-entropy and, with a module (``mtp`` not None), item
    4's."""
    st = dict(statics)
    x = _collapse(X)
    with jax.default_matmul_precision("highest"):
        ce = _ce((_rms_norm(x, out_norm, st["eps"]) @ head)[:, :-1],
                 tokens[:, 1:])
        if mtp is None or not st["mtp_coef"]:
            return ce, None
        following = jnp.concatenate(
            [embed[tokens[:, 1:]], jnp.zeros_like(x[:, :1])], axis=1)
        g = jnp.concatenate([_rms_norm(x, mtp["h_norm"], st["eps"]),
                             _rms_norm(following, mtp["e_norm"], st["eps"])],
                            axis=-1) @ mtp["proj"]
    if X.ndim == 4:
        g = jnp.repeat(g[:, :, None, :], X.shape[2], axis=2)
    g, load = _block(g, mtp["layer"], statics, ffn)
    with jax.default_matmul_precision("highest"):
        logits = _rms_norm(_collapse(g), mtp["out_norm"], st["eps"]) @ head
    return ce + st["mtp_coef"] * _ce(logits[:, :-2], tokens[:, 2:]), load


_tail_jit = jax.jit(_tail, static_argnames=("statics", "ffn"))


def _statics(model: dict, rotated_part=True, sinkhorn_iters=None, bias=True,
             mtp_loss=True, gates_dtype=None):
    """The layer function's static arguments (hashable), and the FFN kind of
    every layer.  The four switches and ``gates_dtype`` exist to show what
    the runner's bounds refuse (the module docstring's "Tolerances")."""
    L = model["n_layers"]
    statics = tuple(sorted(dict(
        heads=model["n_heads"], dn=model["qk_nope_dim"],
        dr=model["qk_rope_dim"], dv=model["v_head_dim"],
        mscale=float(model.get("attn_mscale", 1.0)),
        rope=tuple(sorted((model.get("rope_latent") or {
            "theta": model.get("rope_theta", 10000.0)}).items())),
        eps=float(model.get("norm_eps", 1e-5)),
        clamp=(float(model.get("hc_res_clamp_min", -30.0)),
               float(model.get("hc_res_clamp_max", 30.0))),
        sinkhorn=(int(model.get("hc_sinkhorn_iters", 20))
                  if sinkhorn_iters is None else sinkhorn_iters),
        hc_eps=float(model.get("hc_eps", 1e-6)),
        top_k=model.get("top_k", 2),
        norm_topk_prob=bool(model.get("norm_topk_prob", True)),
        routed_scale=float(model.get("routed_scale", 1.0)),
        experts_first=int(model.get("experts_first", 0)),
        mtp_coef=(float(model.get("mtp_loss_coef", 0.3)) if mtp_loss
                  and model.get("mtp_layers") else 0.0),
        rotated_part=bool(rotated_part), bias=bool(bias),
        gates_dtype=gates_dtype).items()))
    default = "sparse" if model.get("num_experts", 0) else "dense"
    return statics, list(model.get("mlp_layer_types") or [default] * L)


def _streams(params, tokens, model):
    x = params["embed"][tokens]
    n = model.get("hc_mult", 0)
    return jnp.repeat(x[:, :, None, :], n, axis=2) if n else x


def loss(params, tokens, model, **switches):
    """The training loss of ``tokens`` [B, T] (item 4's sum)."""
    statics, ffns = _statics(model, **switches)
    X = _streams(params, tokens, model)
    for i, ffn in enumerate(ffns):
        X, _ = _block_jit(X, layer(params["layers"], i), statics=statics,
                          ffn=ffn)
    return _tail_jit(X, params["out_norm"], params["embed"],
                     params.get("mtp"), params["head"], tokens,
                     statics=statics, ffn=ffns[-1])[0]


def loss_and_grads(params, tokens, model, layers=(0,), **switches):
    """``(loss, grads, bias_after)``: gradients for ``embed``, ``out_norm``,
    the prediction module's leaves (``grads["mtp"]``) and every leaf of the
    layers named (``grads["layers"][i]``, a dict without the layer axis);
    ``bias_after`` the correction bias of every routed layer after the rule
    (``{layer index or "mtp": [E]}``).  Departure (c) of the module
    docstring says how reverse mode is chained."""
    statics, ffns = _statics(model, **switches)
    rate = float(model.get("router_bias_rate", 0.001))
    Xs, loads = [_streams(params, tokens, model)], {}
    for i, ffn in enumerate(ffns):
        X, load = _block_jit(Xs[-1], layer(params["layers"], i),
                             statics=statics, ffn=ffn)
        Xs.append(X)
        if load is not None:
            loads[i] = load
    mtp = params.get("mtp")
    (total, m_load), pull = jax.vjp(
        lambda X, g, e, m: _tail_jit(X, g, e, m, params["head"], tokens,
                                     statics=statics, ffn=ffns[-1]),
        Xs.pop(), params["out_norm"], params["embed"], mtp)
    if m_load is not None:
        loads["mtp"] = m_load
    dX, d_norm, d_embed, d_mtp = pull(
        (jnp.ones_like(total),
         None if m_load is None else np.zeros(m_load.shape,
                                              jax.dtypes.float0)))
    grads = {"out_norm": d_norm, "mtp": d_mtp, "layers": {}}
    for i in reversed(range(len(ffns))):
        lyr, ffn = layer(params["layers"], i), ffns[i]
        if i in layers:
            _, pull = jax.vjp(
                lambda X, l: _block_jit(X, l, statics=statics, ffn=ffn)[0],
                Xs.pop(), lyr)
            dX, grads["layers"][i] = pull(dX)
        else:
            _, pull = jax.vjp(
                lambda X: _block_jit(X, lyr, statics=statics, ffn=ffn)[0],
                Xs.pop())
            dX, = pull(dX)
        del pull
    if dX.ndim == 4:
        dX = jnp.sum(dX, axis=2)
    grads["embed"] = d_embed.at[tokens].add(dX)

    def after(i, load):
        lyr = mtp["layer"] if i == "mtp" else layer(params["layers"], i)
        load = load.astype(jnp.float32)
        return lyr["router_bias"] + rate * jnp.sign(jnp.mean(load) - load)

    return total, grads, {i: after(i, load) for i, load in loads.items()}
