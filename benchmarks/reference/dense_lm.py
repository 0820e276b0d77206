"""Plain reference of the dense decoder the ``lm_train`` runner trains.

embed -> L x (RMSNorm -> causal multi-head attention with rotary positions
-> residual -> RMSNorm -> SwiGLU -> residual) -> RMSNorm -> head -> mean
next-token cross-entropy.  Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: dense [T, T] scores, a Python
loop over the layers, no kernel, no scan, no cache.  One layer is one jitted
function, so a layer's program compiles once and runs L times.  It reads the
program's parameter tree (``embed``, ``out_norm``, ``head`` and ``layers``
with ``wq wk wv wo w1 w3 w2 attn_norm mlp_norm``, either a list of layers
or leaves stacked ``[L, ...]``) and follows the program's rotary convention:
the head dimension is split in halves (x1 | x2), frequency i is
``theta ** (-i / half)``, positions count from 0.

Departures from the published Ouro block are the configuration's, not this
file's: see ``departures`` in ``benchmarks/configs/ouro-2.6b-l16-ut1.json``.

Tolerances (used by ``benchmarks/runners/lm_train.py``, measured on the chip
in PR 22, see PERF.md):

- ``LOSS_ATOL``: the system computes in bfloat16 with float32 accumulation;
  a logit then carries ~2^-8 relative error, which the mean over the
  sample's 2047 positions averages down.  Measured on the chip at the
  published widths: |difference| 5e-5 at a loss of 11.31.  The bound is
  well above that and below what a wrong mask, rotation or norm moves the
  loss by (a rotary base of 1e4 for 1e6 moves a toy model's by more).
- ``GRAD_RTOL``: relative L2 distance between (old - new) / lr of a sampled
  leaf and the reference's gradient.  Measured on the chip through 16
  layers: 1.3% (final norm gain) to 4.0% (the wq tile), the same on two
  seeds; bfloat16 keeps 8 bits, 2^-9 = 0.2% a rounding, and a gradient
  passes some hundreds of roundings.  The bound is twice the worst leaf.
  An 8-bit float format (three mantissa bits, 6% a rounding) or bfloat16
  accumulation over the 2048-long dot products gives several times it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["loss", "loss_and_grads", "LOSS_ATOL", "GRAD_RTOL"]

LOSS_ATOL = 1e-2
GRAD_RTOL = 8e-2


def _layer(params, i):
    layers = params["layers"]
    if isinstance(layers, (list, tuple)):
        return layers[i]
    return {k: v[i] for k, v in layers.items()}


def _rms_norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _rotary(x, theta):
    """x [T, H, D]."""
    T, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(x, lyr, heads: int, eps: float, theta: float):
    """One decoder layer on one sequence, x [T, dim]."""
    T, dim = x.shape
    head_dim = dim // heads
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, lyr["attn_norm"], eps)
        q = _rotary((h @ lyr["wq"]).reshape(T, heads, head_dim), theta)
        k = _rotary((h @ lyr["wk"]).reshape(T, heads, head_dim), theta)
        v = (h @ lyr["wv"]).reshape(T, heads, head_dim)
        s = jnp.einsum("thd,shd->hts", q, k) * head_dim ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        x = x + o.reshape(T, dim) @ lyr["wo"]
        h = _rms_norm(x, lyr["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ lyr["w1"]) * (h @ lyr["w3"])) @ lyr["w2"]


def _head_loss(x, out_norm, head, tokens, eps: float):
    """Summed next-token cross-entropy of one sequence from its last
    hidden states x [T, dim]."""
    with jax.default_matmul_precision("highest"):
        logits = _rms_norm(x, out_norm, eps) @ head
    logz = jax.nn.logsumexp(logits[:-1], axis=-1)
    picked = jnp.take_along_axis(logits[:-1], tokens[1:, None], axis=-1)[:, 0]
    return jnp.sum(logz - picked)


# One layer's program is compiled once and run L times: the Python loop over
# the layers stays outside jit, so compiling costs seconds, not minutes.
_block_jit = jax.jit(_block, static_argnames=("heads", "eps", "theta"))
_head_loss_jit = jax.jit(_head_loss, static_argnames=("eps",))


def _statics(model):
    return dict(heads=model["n_heads"], eps=float(model["norm_eps"]),
                theta=float(model["rope_theta"]))


def loss(params, tokens, model):
    """Mean cross-entropy over every next-token position of ``tokens``
    [B, T], one sequence and one layer at a time."""
    B, T = tokens.shape
    st = _statics(model)
    total = 0.0
    for b in range(B):
        x = params["embed"][tokens[b]]
        for i in range(model["n_layers"]):
            x = _block_jit(x, _layer(params, i), **st)
        total = total + _head_loss_jit(x, params["out_norm"], params["head"],
                                       tokens[b], eps=st["eps"])
    return total / (B * (T - 1))


def loss_and_grads(params, tokens, model, layer: int):
    """``(loss, grads)`` with gradients for ``embed``, ``out_norm`` and
    every leaf of layer ``layer`` (a dict without the layer axis): the
    leaves the runner samples.  Reverse mode is ``jax.vjp`` of the plain
    functions above, chained over the layers by hand; the other layers'
    weight gradients are not formed.  The forward keeps each layer's input
    only, and the backward runs a layer's forward again to differentiate
    it: the same function on the same input, so the numbers are those of
    one pass, and one layer's activations are held at a time (16 layers'
    dense float32 scores at 2048 tokens do not fit beside the weights)."""
    B, T = tokens.shape
    st = _statics(model)
    total = 0.0
    grads = {"embed": jnp.zeros_like(params["embed"]),
             "out_norm": jnp.zeros_like(params["out_norm"]), "layer": None}
    for b in range(B):
        tok = tokens[b]
        xs = [params["embed"][tok]]
        for i in range(model["n_layers"]):
            xs.append(_block_jit(xs[-1], _layer(params, i), **st))
        val, pull = jax.vjp(
            lambda x, g: _head_loss_jit(x, g, params["head"], tok,
                                        eps=st["eps"]),
            xs.pop(), params["out_norm"])
        total = total + val
        dx, d_norm = pull(jnp.ones_like(val))
        grads["out_norm"] = grads["out_norm"] + d_norm
        for i in reversed(range(model["n_layers"])):
            lyr = _layer(params, i)
            if i == layer:
                _, pull = jax.vjp(lambda x, l: _block_jit(x, l, **st),
                                  xs.pop(), lyr)
                dx, d_layer = pull(dx)
                grads["layer"] = d_layer if grads["layer"] is None else (
                    jax.tree_util.tree_map(jnp.add, grads["layer"], d_layer))
            else:
                _, pull = jax.vjp(lambda x: _block_jit(x, lyr, **st),
                                  xs.pop())
                dx, = pull(dx)
            del pull
        grads["embed"] = grads["embed"].at[tok].add(dx)
    scale = 1.0 / (B * (T - 1))
    return total * scale, jax.tree_util.tree_map(lambda g: g * scale, grads)
