"""Plain reference of OLMoE-1B-7B's decoder (arXiv:2409.02060; HF
``modeling_olmoe``), the model ``lm_train`` trains for the configuration
``olmoe-1b-7b-e64``.

    h  = RMSNorm(x; g_attn)
    q  = RMSNorm(h Wq; g_q)       QK-norm: over the whole projection, before
    k  = RMSNorm(h Wk; g_k)         the split into heads and before rotary
    x  = x + CausalMHA(rope(q), rope(k), h Wv) Wo
    h  = RMSNorm(x; g_mlp)
    p  = softmax(h Wr)                                  [N, E], float32
    (w_1..w_k, e_1..e_k) = top_k(p)                     not renormalised
    x  = x + sum_j w_j W2[e_j] (silu(W1[e_j] h) * W3[e_j] h)
    loss = CE + c_b sum_layers E sum_e f_e P_e + c_z sum_layers mean_tokens lse(h Wr)^2

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense [T, T] scores, a Python
loop over the layers and, for the FFN, a loop over the experts (a
``lax.scan`` over the expert axis of the weights) in which **every expert
computes every token** and a 0/1 mask (times the route's weight) keeps what
was routed: no sort, no grouped matmul, no capacity, nothing dropped.  One layer is one jitted function.  It reads the program's
parameter tree (``embed``, ``out_norm``, ``head`` and ``layers`` with ``wq wk
wv wo attn_norm mlp_norm q_norm k_norm router w1 w3 w2``, a list of layers or
leaves stacked ``[L, ...]``) and the program's rotary convention
(``dense_lm.py``).  ``model`` is the configuration's ``model`` group; a key
it leaves out has ``TransformerConfig``'s default.

Departures from the published description, all of them the program's too:

- the balancing term is one product a layer, summed over the layers:
  each layer's ``f_e`` and ``P_e`` are taken over that layer's tokens.  HF
  ``load_balancing_loss_func`` pools the router logits of all layers
  first and forms one product from the pooled ``f_e`` and ``P_e``, which is
  about 1/L of this sum (L = 16 at the published depth) and does not see
  one layer's imbalance cancelled by another's.  The paper's coefficient
  0.01 is applied to the sum.
- ``f_e`` is the share of tokens with a route to ``e`` (so it sums to k),
  as in HF; the paper's equation divides by k.

**The router flips.**  Top-8 of 64 probabilities: where a token's 8th and
9th largest lie closer than the system's rounding moves them, the system
routes it elsewhere than this reference does, and both are right.  That is
not hidden by handing the reference the system's routes.  It is measured:
``router_input_dtype=jnp.bfloat16`` rounds the router's *input* (the
normalised hidden state, which the system holds in bfloat16) before the
float32 router matmul, so the distance system-to-reference can be read with
and without that one cause.

Tolerances (used by ``benchmarks/runners/lm_train.py`` against the plain
reference; measured on the chip in PR 26 at the published widths, 3 layers,
one sequence of 1024 Zipf tokens, gradients of layer 1; ``PERF.md`` section 6):

- ``LOSS_ATOL``: as ``dense_lm.py``.  Measured |difference| 1e-5 to 1.6e-3
  over 17 seeds at a loss of 11.9-12.3.
- ``GRAD_RTOL``: relative L2 distance between (old - new) / lr of a sampled
  leaf and this file's gradient.  Measured over 17 seeds (learning rates
  0.1, 0.01 and the cell's 0.002): ``out_norm`` 0.6-0.9%, ``embed``
  1.2-1.6%, ``attn_norm`` 0.9-1.3%, ``mlp_norm`` 0.8-2.2%, the ``wq`` tile
  1.9-3.3%, the ``w2`` tile (all 64 experts, 256 x 2048 each) 1.4-2.7%.  **The flips:** with the router's input rounded to
  bfloat16 the same distances read (seed 2) ``w2`` 1.47% for 1.41%, ``wq``
  3.15% for 3.25%, loss 4.1e-4 for 6.8e-4: at these widths (router logits
  of spread 0.9, 128 rows of the sample an expert) swapped 8th and 9th
  experts are inside the bfloat16 noise, not above it, and rounding the
  router's input alone explains none of it.  At toy widths they are not:
  ``tests/test_olmoe.py`` reads 10-14% on the routed leaves at width 32,
  where an expert sees 16 rows.  The bound is 8%, 2.4 times the worst leaf
  (``dense_lm.py``'s is twice its worst).  What it refuses, same sample:
  a schedule that drops routes (``capacity`` at factor 2.0 drops 46% of this
  Zipf sample's routes: ``w2`` 70%, ``wq`` 27%, loss off by 0.027; at 1.0,
  65%: ``w2`` 85%), and this reference with its weights in the precision
  below (rounded through float8 e4m3): ``wq`` 21%, ``w2`` 15%, every leaf
  above 8.7%.  This reference computed wholly in bfloat16 (float32
  accumulation on the MXU) is *not* refused (``w2`` 1.4%, ``wq`` 3.4%): that
  is the configuration's own precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.dense_lm import (_head_loss, _layer, _rms_norm,
                                           _rotary)

__all__ = ["loss", "loss_and_grads", "LOSS_ATOL", "GRAD_RTOL"]

LOSS_ATOL = 1e-2
GRAD_RTOL = 8e-2

_STATIC = ("heads", "eps", "theta", "top_k", "norm_topk_prob", "qk_norm",
           "balance_coef", "z_coef", "router_input_dtype")


def _attention(x, lyr, heads, eps, theta, qk_norm):
    """Pre-norm causal multi-head attention with residual; x [B, T, dim]."""
    B, T, dim = x.shape
    head_dim = dim // heads
    h = _rms_norm(x, lyr["attn_norm"], eps)
    q, k, v = h @ lyr["wq"], h @ lyr["wk"], h @ lyr["wv"]
    if qk_norm:
        q = _rms_norm(q, lyr["q_norm"], eps)
        k = _rms_norm(k, lyr["k_norm"], eps)
    rope = jax.vmap(lambda t: _rotary(t.reshape(T, heads, head_dim), theta))
    q, k = rope(q), rope(k)
    v = v.reshape(B, T, heads, head_dim)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * head_dim ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, axis=-1), v)
    return x + o.reshape(B, T, dim) @ lyr["wo"]


def _experts(x, lyr, eps, top_k, norm_topk_prob, router_input_dtype):
    """The routed FFN with residual, and the layer's two auxiliary terms:
    ``(x_out, balance, z)``; x [B, T, dim]."""
    B, T, dim = x.shape
    h = _rms_norm(x, lyr["mlp_norm"], eps).reshape(B * T, dim)
    E = lyr["router"].shape[1]
    routed = h
    if router_input_dtype is not None:
        routed = h.astype(router_input_dtype).astype(jnp.float32)
    logits = routed @ lyr["router"]                              # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_idx = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    def one_expert(y, expert):
        e, w1, w3, w2 = expert
        mine = top_idx == e                                      # [N, k] 0/1
        weight = jnp.sum(jnp.where(mine, top_p, 0.0), axis=-1)   # [N]
        out = (jax.nn.silu(h @ w1) * (h @ w3)) @ w2      # every token
        share = jnp.mean(jnp.any(mine, axis=-1).astype(jnp.float32))
        return y + weight[:, None] * out, share

    # the loop over the experts, as a scan so that one expert's program
    # compiles once (unrolled, 64 of them took three minutes to compile)
    y, share = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(E), lyr["w1"], lyr["w3"], lyr["w2"]))
    balance = E * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return x + y.reshape(B, T, dim), balance, z


def _block(x, lyr, heads, eps, theta, top_k, norm_topk_prob, qk_norm,
           balance_coef, z_coef, router_input_dtype):
    """One decoder layer on a batch: ``(x_out, the layer's weighted
    auxiliary loss)``."""
    with jax.default_matmul_precision("highest"):
        x = _attention(x, lyr, heads, eps, theta, qk_norm)
        x, balance, z = _experts(x, lyr, eps, top_k, norm_topk_prob,
                                 router_input_dtype)
    return x, balance_coef * balance + z_coef * z


_block_jit = jax.jit(_block, static_argnames=_STATIC)


def _ce(x, out_norm, head, tokens, eps):
    """Mean next-token cross-entropy from the last hidden states [B, T, dim]."""
    B, T = tokens.shape
    total = sum(_head_loss(x[b], out_norm, head, tokens[b], eps)
                for b in range(B))
    return total / (B * (T - 1))


_ce_jit = jax.jit(_ce, static_argnames=("eps",))


def _statics(model, router_input_dtype):
    return dict(heads=model["n_heads"],
                eps=float(model.get("norm_eps", 1e-5)),
                theta=float(model.get("rope_theta", 10000.0)),
                top_k=model.get("top_k", 2),
                norm_topk_prob=bool(model.get("norm_topk_prob", True)),
                qk_norm=bool(model.get("qk_norm", False)),
                balance_coef=float(model.get("aux_loss_coef", 0.01)),
                z_coef=float(model.get("router_z_loss_coef", 0.0)),
                router_input_dtype=router_input_dtype)


def loss(params, tokens, model, router_input_dtype=None):
    """Cross-entropy (mean over every next-token position of ``tokens``
    [B, T]) plus the weighted auxiliary terms of every layer."""
    st = _statics(model, router_input_dtype)
    x = params["embed"][tokens]
    aux = 0.0
    for i in range(model["n_layers"]):
        x, a = _block_jit(x, _layer(params, i), **st)
        aux = aux + a
    return _ce_jit(x, params["out_norm"], params["head"], tokens,
                   eps=st["eps"]) + aux


def loss_and_grads(params, tokens, model, layer: int,
                   router_input_dtype=None):
    """``(loss, grads)`` with gradients for ``embed``, ``out_norm`` and
    every leaf of layer ``layer`` (a dict without the layer axis): the
    leaves the runner samples.  Reverse mode is ``jax.vjp`` of the plain
    functions above, chained over the layers by hand as in ``dense_lm.py``:
    each layer's forward runs again in the backward, one layer's
    activations are held at a time, and the other layers' weight gradients
    are not formed.  A layer's auxiliary loss enters the total with
    cotangent 1."""
    st = _statics(model, router_input_dtype)
    xs = [params["embed"][tokens]]
    aux = 0.0
    for i in range(model["n_layers"]):
        x, a = _block_jit(xs[-1], _layer(params, i), **st)
        xs.append(x)
        aux = aux + a
    ce, pull = jax.vjp(
        lambda x, g: _ce_jit(x, g, params["head"], tokens, eps=st["eps"]),
        xs.pop(), params["out_norm"])
    dx, d_norm = pull(jnp.ones_like(ce))
    grads = {"out_norm": d_norm, "layer": None}
    one = jnp.ones((), jnp.float32)
    for i in reversed(range(model["n_layers"])):
        lyr = _layer(params, i)
        if i == layer:
            _, pull = jax.vjp(lambda x, l: _block_jit(x, l, **st),
                              xs.pop(), lyr)
            dx, grads["layer"] = pull((dx, one))
        else:
            _, pull = jax.vjp(lambda x: _block_jit(x, lyr, **st), xs.pop())
            dx, = pull((dx, one))
        del pull
    grads["embed"] = jnp.zeros_like(params["embed"]).at[tokens].add(dx)
    return ce + aux, grads
