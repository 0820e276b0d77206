"""Plain reference of EvaByte's decoder
(https://huggingface.co/EvaByte/EvaByte/blob/main/config.json; ``model_type``
``evabyte``, ``attention_class`` ``eva``; EVA is arXiv:2302.04542), the model
``lm_train_eva`` trains for the configuration ``evabyte-6.5b-l4``.  d =
``hidden_size``, H heads of D = d / H, V = ``vocab_size`` byte ids, P =
``num_pred_heads``:

1. Block, pre-norm, the sums in float32 (``fp32_skip_add``)::

       x <- x + attn(norm1(x));   x <- x + mlp(norm2(x))
       norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)          norm_add_unit_offset
       mlp(h) = (SiLU(h W1) * (h W3)) W2                       SwiGLU, no bias

2. ``attn``, a head h, scale s = D^-0.5, window W = ``window_size``, chunk c =
   ``chunk_size``::

       q, k = rope(h Wq), rope(h Wk)      whole head rotated (halves), theta
       v = h Wv
       chunk m = positions [c m, c m + c):
           a_j = softmax_{j in m}(s k_j . phi_h)
           kbar_m = sum_j a_j k_j + mu_h;     vbar_m = sum_j a_j v_j
       query t, window w = t // W, sees S_t = {j : W w <= j <= t} exactly and
       R_t = {m : m < (W / c) w} as summaries, in ONE softmax:
           o_t = (sum_S e^{s q_t.k_j} v_j + sum_R e^{s q_t.kbar_m} vbar_m)
                 / (sum_S e^{s q_t.k_j} + sum_R e^{s q_t.kbar_m})
       y = o Wo

3. Head and loss: ``logits_i = norm(x) W_head[:, V i : V (i + 1)]``, i = 0 ..
   P - 1, float32; ``loss = mean_i mean_t CE(logits_i[t], byte[t + 1 + i])``
   over the positions t that have the target.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no scan, a Python loop
over the layers and, inside a layer, over the windows, each a dense masked
softmax over ``[own window | summaries of the earlier windows]`` (a window and
a layer are ``jax.checkpoint``ed, so that one window's scores are alive at a
time and 1 x 8,192 at the published widths fits beside the program's own
copy).  It is written from the equations above; of ``multiverso_tpu`` it reads
the parameter tree's names (``embed``, ``head``, ``out_norm``; a layer's
``wq wk wv wo phi mu w1 w3 w2 attn_norm mlp_norm``, stacked ``[L, ...]``) and
the rotary layout (halves), and no line of ``models/`` or ``ops/``.

**Switches** (``_statics``), which make it the program in a precision BELOW the
one the configuration states, for the runner's controls: ``compute`` (the
dtype matmul operands and sub-layer outputs are rounded to: the program's
bfloat16; alone it is no fault), ``residual`` (the stream's dtype: bfloat16 is
``fp32_skip_add`` false), ``stats`` (the dtype of the attention's scores,
their exponentials and normaliser, and of the pooling's softmax: bfloat16 is
``mixedp_attn`` false), ``logits`` (bfloat16 is ``fp32_logits`` false).

Tolerances (``benchmarks/runners/lm_train_eva.py``; measured on the chip in
PR 40 at the published widths: nineteen seeds of the program, five through
the runner's tool (41, 2100000011; after the review 2900000011, 3300000029, 41
again) and fourteen runs of the cell, the controls on the tool's five;
``PERF.md`` section 6).  A wrong program's reading is this file's own in a precision below
the stated one (the runner's ``CONTROLS``: the program's bfloat16 operands and
one guarantee dropped) put in the program's place.  Each wrong program fails at
least one check, not each.

**The attention alone on scaled inputs** (``attention_and_grads`` against
``ops/flash_eva.py``'s summariser and kernels on the runner's
``scaled_inputs``: one sequence of 8,192 positions, 32 heads of 128, normals
1.5 wide, ``phi`` and ``mu`` of unit size; bfloat16 operands on both sides).
This is the set on which a lower precision of the softmax shows in every
number:

- ``ATTN_SCALED_RTOL`` 0.006, the output's relative L2 distance: the program
  reads 0.00347 to 0.00349 over the nineteen seeds (the reference with bfloat16
  operands alone 0.0017: the rest is the kernel's bfloat16 output and
  pre-scaled q).  **Bfloat16 softmax statistics** (``mixedp_attn`` false:
  scores, exponentials and the pooling's softmax rounded) read 0.00927 to
  0.00932 over five seeds: 2.66 times the program, and neither reading moves
  in its third digit from seed to seed, so the limit stands 1.7 times above
  the one and 1.55 below the other.
- ``ATTN_SCALED_GRAD_RTOL`` 0.010, the worst of the five gradients (q, k, v,
  phi, mu): the program reads 0.00458 to 0.00520 (``dphi``), **bfloat16
  statistics** 0.0169 to 0.0196 (``dphi``; ``dq``, ``dk``, ``dv`` 0.0090 to
  0.0093).

**The attention alone on the timed model's own inputs** (the runner's
``layer_inputs``: the last layer's q, k, v at the check batch from
``attention_inputs`` on the program's weights, that layer's ``phi`` and
``mu``; ten seeds of the program after the review, three through the tool and
seven runs of the cell, the controls on the tool's three).  At the published
initialisation the scores are a fraction of a unit and the pooling is near
uniform, so the output hardly shows a rounding (program 0.00165 to 0.00167,
bfloat16 statistics 0.00167 to 0.00168) and ``dq`` is a difference of nearly
equal sums (program 0.0167 to 0.0226, the worst of the five; bfloat16
statistics 0.032 to 0.035, 1.4 to 2.1 times: no room for a limit between):

- ``ATTN_RTOL`` 0.003 and ``ATTN_GRAD_RTOL`` 0.04 stand 1.8 times above the
  program's largest and have NO upper reading: they refuse what is wrong
  outright on the model's own distribution (a tile of the staircase, a term of
  the pooling), not a precision.
- ``ATTN_PHI_RTOL`` 0.008, ``dphi`` alone, the one gradient that tells the
  statistics' precision on these inputs: the program reads 0.0034 to 0.0047
  over the ten seeds, **bfloat16 statistics 0.0133 to 0.0184** over three (2.8
  times the program's largest at the least; the limit 1.7 times from either).
  The cell's seven runs were made at 0.0075 / 0.035, the tool's first readings;
  the seventh run's 0.0047 and 0.0226 moved both to where they stand.

**The step's loss and gradients** (4 layers, 1 x 8,192 Zipf byte ids: four
windows; every leaf, 47 readings; 16 rows of logits).  These four have NO upper
reading: every control reads as the program does here, because four layers of
bfloat16 matmuls cost as much as any one of them.  They stand 1.8 to 2 times
above the program's largest over the nineteen seeds and refuse what is wrong
outright (a tile of the staircase, a term of the pooling, a head's offset):

- ``LOSS_ATOL`` 1e-2: measured |difference| 0.00001 to 0.0012 at a loss of
  6.1; the controls 0.0002 to 0.0012.  Kept at the limit of the harness's
  accepted cells (Ling's), which leaves the first reading eight times of
  room, as the contract has it for a loss.
- ``LOGITS_RTOL`` 0.02, on the MEDIAN of the 16 rows' relative L2 distances:
  0.0097 to 0.0110; the controls 0.0098 to 0.0110.
- ``GRAD_RTOL`` 0.05, every leaf but ``phi`` and ``mu``: a seed's worst reads
  0.014 to 0.0267 (``wk`` and ``wq``, whose gradients pass the softmax).
- ``GRAD_RTOL_POOL`` 0.036 (0.05 before the review), ``phi`` and ``mu`` (they
  learn through the summaries alone): 0.0155 to 0.0179; bfloat16 statistics
  0.0175 to 0.0253.
- **Not told apart by any number: a bfloat16 residual** (``fp32_skip_add``
  false: median logits 0.0108 to 0.0110 for the program's 0.0097 to 0.0110,
  worst leaf 0.018 to 0.020 for 0.014 to 0.0245) **and bfloat16 logits** (as
  the program to three digits).  No limit stands between them with room, so
  the runner reads both guarantees off the timed step's own text
  (``lm_train_eva.stream_dtypes``: a walk that follows the stream from sum to
  sum and finds it nowhere rounded, the scan's carry float32, float32
  logits), where they are exact.

**The timed program's step** (``lm_train_eva.step_compare``: one
``train_step_async`` at 1 x 8,192, every sampled leaf against ``old - lr *
gradient`` in float32, the gradient the one the limits above hold):
``STEP_RTOL`` 0.1 on ``|new - wanted| / |old - wanted|``.  The program reads
**0 on all 47 leaves of ten seeds** (the step's program and the
gradient's agree to the bit, and its loss is the same float); no leaf's
wanted step rounds to nothing.  A leaf left as it was reads 1, twice the rate
1.0 to 2.06, another gradient 1.37 to 1.46, the wrong way 2
(``step_controls``, the same three seeds).  The limit leaves room for a
compiler that fuses the two programs unlike (a float32 spacing here and there
reads 0.02 to 0.06 by the leaves' step sizes) and stands ten times under the
mildest fault.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["forward", "loss", "loss_and_grads", "attention_and_grads",
           "attention_inputs", "LOSS_ATOL", "LOGITS_RTOL", "GRAD_RTOL",
           "GRAD_RTOL_POOL", "STEP_RTOL", "ATTN_RTOL", "ATTN_GRAD_RTOL",
           "ATTN_PHI_RTOL", "ATTN_SCALED_RTOL", "ATTN_SCALED_GRAD_RTOL"]

LOSS_ATOL = 1e-2
LOGITS_RTOL = 0.02
GRAD_RTOL = 0.05
GRAD_RTOL_POOL = 0.036
STEP_RTOL = 0.1
ATTN_RTOL = 0.003
ATTN_GRAD_RTOL = 0.04
ATTN_PHI_RTOL = 0.008
ATTN_SCALED_RTOL = 0.006
ATTN_SCALED_GRAD_RTOL = 0.010

_F32 = jnp.float32


def _statics(compute=None, residual=_F32, stats=_F32, logits=_F32):
    return compute, jnp.dtype(residual), jnp.dtype(stats), jnp.dtype(logits)


def _mm(x, w, compute):
    """``x @ w``: float32 at "highest", or with ``compute`` its operands and
    its result rounded to that dtype (float32 accumulation)."""
    if compute is None:
        return x @ w
    return jnp.dot(x.astype(compute), w.astype(compute),
                   preferred_element_type=_F32).astype(compute).astype(_F32)


def _norm(x, w, eps, compute):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if compute is None:
        return y * (1.0 + w)
    gain = (1.0 + w).astype(compute).astype(_F32)
    return (y.astype(compute).astype(_F32) * gain).astype(compute).astype(_F32)


def _rotary(x, theta: float):
    """x [B, T, H, D], every dim rotated (halves x1 | x2)."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half),
                        _F32)
    ang = jnp.arange(T, dtype=_F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _summaries(k, v, phi, mu, scale, chunk, stats):
    """``(kbar, vbar)`` [B, T / chunk, H, D] of k, v [B, T, H, D]."""
    B, T, H, D = k.shape
    kc = k.reshape(B, T // chunk, chunk, H, D)
    vc = v.reshape(B, T // chunk, chunk, H, D)
    logits = (scale * jnp.einsum("bmchd,hd->bmch", kc, phi)).astype(stats)
    a = jax.nn.softmax(logits, axis=2).astype(_F32)
    return (jnp.einsum("bmch,bmchd->bmhd", a, kc) + mu[None, None],
            jnp.einsum("bmch,bmchd->bmhd", a, vc))


def _round(x, compute):
    return x if compute is None else x.astype(compute).astype(_F32)


def _window(q, keys, values, own: int, scale, compute, stats):
    """One window's queries q [B, Wq, H, D] over ``keys`` = [its own ``own``
    keys | the summaries it sees]: a dense masked softmax."""
    s = (scale * jnp.einsum("bqhd,bkhd->bhqk", q, keys)).astype(stats)
    pos_q = jnp.arange(q.shape[1])[:, None]
    pos_k = jnp.arange(keys.shape[1])[None, :]
    s = jnp.where((pos_k >= own) | (pos_k <= pos_q), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(_F32)
    return jnp.einsum("bhqk,bkhd->bqhd", _round(p, compute), values)


def _attention(q, k, v, phi, mu, model, compute, stats):
    """q, k (rotated), v [B, T, H, D] → o [B, T, H, D]."""
    W, c = model["eva_window"], model["eva_chunk"]
    T, D = q.shape[1], q.shape[-1]
    scale, per = D ** -0.5, W // c
    kbar, vbar = _summaries(k, v, phi, mu, scale, c, stats)
    kbar, vbar = _round(kbar, compute), _round(vbar, compute)
    window = jax.checkpoint(functools.partial(
        _window, scale=scale, compute=compute, stats=stats),
        static_argnums=(3,))
    out = []
    for w in range(-(-T // W)):
        own = slice(W * w, min(W * (w + 1), T))
        out.append(window(
            q[:, own],
            jnp.concatenate([k[:, own], kbar[:, :per * w]], axis=1),
            jnp.concatenate([v[:, own], vbar[:, :per * w]], axis=1),
            own.stop - own.start))
    return jnp.concatenate(out, axis=1)


def _qkv(x, lyr, model, compute):
    """A layer's q, k (rotated) and v [B, T, H, D] of its input x."""
    B, T, d = x.shape
    H, theta = model["n_heads"], model["rope_theta"]
    mm = functools.partial(_mm, compute=compute)
    h = _norm(x.astype(_F32), lyr["attn_norm"], model["norm_eps"], compute)
    heads = lambda y: y.reshape(B, T, H, d // H)
    return (_round(_rotary(heads(mm(h, lyr["wq"])), theta), compute),
            _round(_rotary(heads(mm(h, lyr["wk"])), theta), compute),
            heads(mm(h, lyr["wv"])))


def _layer(x, lyr, model, statics):
    compute, residual, stats, _ = statics
    B, T, d = x.shape
    eps = model["norm_eps"]
    mm = functools.partial(_mm, compute=compute)
    q, k, v = _qkv(x, lyr, model, compute)
    o = _round(_attention(q, k, v, lyr["phi"], lyr["mu"], model, compute,
                          stats), compute)
    x = (x + mm(o.reshape(B, T, d), lyr["wo"]).astype(residual))
    h = _norm(x.astype(_F32), lyr["mlp_norm"], eps, compute)
    gated = _round(jax.nn.silu(mm(h, lyr["w1"])) * mm(h, lyr["w3"]), compute)
    return x + mm(gated, lyr["w2"]).astype(residual)


def forward(params, tokens, model: dict, **switches):
    """tokens [B, T] → float32 logits [B, T, P * V]."""
    statics = _statics(**switches)
    compute, residual, _, logits_dtype = statics
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(residual)
        layer = jax.checkpoint(functools.partial(_layer, model=model,
                                                 statics=statics))
        for i in range(model["n_layers"]):
            x = layer(x, jax.tree_util.tree_map(lambda a: a[i],
                                                params["layers"]))
        h = _norm(x.astype(_F32), params["out_norm"], model["norm_eps"],
                  compute)
        if compute is None:
            logits = h @ params["head"]
        else:
            logits = jnp.dot(h.astype(compute), params["head"].astype(compute),
                             preferred_element_type=_F32)
        return logits.astype(logits_dtype).astype(_F32)


def _loss_of_logits(logits, tokens, model):
    V, P, T = model["vocab_size"], model["n_pred_heads"], tokens.shape[1]
    total = 0.0
    for i in range(P):
        lf = logits[:, :T - 1 - i, V * i:V * (i + 1)]
        target = tokens[:, 1 + i:]
        logz = jax.nn.logsumexp(lf, axis=-1)
        picked = jnp.take_along_axis(lf, target[..., None], axis=-1)[..., 0]
        total = total + jnp.mean(logz - picked)
    return total / P


def loss(params, tokens, model: dict, **switches):
    return _loss_of_logits(forward(params, tokens, model, **switches), tokens,
                           model)


@functools.partial(jax.jit, static_argnames=("model_items", "positions",
                                             "switch_items"))
def _loss_and_grads(params, tokens, model_items, positions, switch_items):
    model, switches = dict(model_items), dict(switch_items)

    def f(p):
        logits = forward(p, tokens, model, **switches)
        return (_loss_of_logits(logits, tokens, model),
                logits[:, np.asarray(positions)])

    (total, rows), grads = jax.value_and_grad(f, has_aux=True)(params)
    return total, grads, rows


def _hashable(d: dict):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()
                        if not isinstance(v, dict) and v is not None))


def loss_and_grads(params, tokens, model: dict, positions=(0,), **switches):
    """``(loss, gradient tree like params, logits rows [B, len(positions), P
    V])`` of one batch."""
    switches = {k: (None if v is None else jnp.dtype(v).name)
                for k, v in switches.items()}
    return _loss_and_grads(params, tokens, _hashable(model),
                           tuple(int(p) for p in positions),
                           _hashable(switches))


@functools.partial(jax.jit, static_argnames=("model_items", "layer"))
def _attention_inputs(params, tokens, model_items, layer):
    model, statics = dict(model_items), _statics()
    at = lambda i: jax.tree_util.tree_map(lambda a: a[i], params["layers"])
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for i in range(layer):
            x = _layer(x, at(i), model, statics)
        return tuple(jnp.swapaxes(y, 1, 2)
                     for y in _qkv(x, at(layer), model, None))


def attention_inputs(params, tokens, model: dict, layer: int):
    """``(q, k, v)`` [B, H, T, D] (``attention_and_grads``' layout, q and k
    rotated) that layer ``layer`` hands its attention on ``tokens``."""
    return _attention_inputs(params, tokens, _hashable(model), int(layer))


def attention_and_grads(q, k, v, phi, mu, d_o, model: dict, **switches):
    """The attention alone: ``(o, (dq, dk, dv, dphi, dmu))`` of q, k, v, d_o
    [B, H, T, D] (the program's layout; q and k as rotated) and phi, mu [H,
    D], the gradients those of ``sum(o * d_o)``."""
    compute, _, stats, _ = _statics(**switches)

    def f(q, k, v, phi, mu):
        t = lambda y: jnp.swapaxes(y.astype(_F32), 1, 2)
        return jnp.swapaxes(_attention(t(q), t(k), t(v), phi, mu, model,
                                       compute, stats), 1, 2)

    with jax.default_matmul_precision("highest"):
        o, pull = jax.vjp(f, q, k, v, phi, mu)
        return o, pull(jnp.asarray(d_o, _F32))
