"""``linear_attention`` (Kimi Delta Attention, arXiv:2510.26692): a recurrent
state a head through the chunked scan of ``ops/kda.py``, no softmax.
``heads`` heads of ``head_dim`` keys and values: q, k, v = SiLU(causal
depthwise conv of ``linear_conv_kernel`` taps (h wq | wk | wv)); q, k
L2-normalised a head, q times head_dim^-0.5; log-decay a head, channel and
token ``kda_lower_bound * sigmoid(exp(A_log) * (h wf + dt_bias))`` (the
bounded gate: the decay lies in (exp(kda_lower_bound), 1)); ``beta =
sigmoid(h wb)`` a head; the scan; an RMSNorm a head (gain ``o_norm
[head_dim]``), then the caller's ``attn_gate`` and ``wo``.  One device: the
scan's state is not handed along an ``sp`` ring and no layout of its heads
over ``tp`` is written.

**Layout** (ISSUE 47).  Every array of the sub-layer that is ``heads *
head_dim`` wide stays ``[B, T, heads * head_dim]`` from its projection to the
scan and from the scan to ``wo``: that is the layout the projections write,
the kernels of ``ops/kda.py`` read through their index maps, and ``wo``
multiplies.  On a TPU ``[T, heads * head_dim]`` and ``[T, heads, head_dim]``
are different tilings of memory (the minor two dimensions are tiled (8, 128):
rows of ``T`` in one, rows of ``heads`` in the other), so a reshape between
them is a copy of the whole array, 256 MiB in float32 at 16,384 tokens, which
autodiff mirrors in the backward and remat "full" runs again.  So a head's
reduction (the L2 norms, the output norm's mean of squares) is
``common.head_sum`` and a head's broadcast (the ``rsqrt`` back to its
channels, the caller's gate) ``common.head_spread``: products with a constant
0/1 matrix, the same float32 sums in another order.  The decay's
``exp(A_log)`` and the output norm's gain are repeated to ``heads *
head_dim`` once, as vectors.  ``kda`` keeps its ``[B, T, H, d]`` signature:
the reshape before a call that flattens again is a pair of bitcasts, which
XLA removes.  ``_heads`` counts a trace in
``attention.linear_flat_traced{heads=}``; ``tests/test_ling.py`` holds the
sub-layer to the 4-D form and the v5e program to no relayout copy."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ... import metrics
from ..common import (AttnKind, elem, head_spread, head_sum, proj,
                      unit_gain)

__all__ = ["LINEAR"]


def _check(cfg, kind):
    if cfg.n_kv_heads or cfg.qk_norm:
        raise ValueError(
            "linear_attention layers take no n_kv_heads or "
            "qk_norm: every head has its own key and value, "
            "and q and k are L2-normalised a head")
    if cfg.linear_conv_kernel < 1 or cfg.kda_lower_bound >= 0:
        raise ValueError(
            "linear_attention layers need linear_conv_kernel "
            ">= 1 and kda_lower_bound < 0")


def _init(cfg, kind, w):
    heads, q_width = kind.heads, kind.heads * cfg.head_dim
    taps = cfg.linear_conv_kernel
    lyr = {name: w(name, cfg.dim, q_width)
           for name in ("wq", "wk", "wv", "wf")}
    lyr.update({name: w(name, taps, q_width, scale=taps ** -0.5)
                for name in ("conv_q", "conv_k", "conv_v")})
    # The decay's time-scales as the flash-linear-attention library
    # draws them: exp(A_log) uniform in (1, 16) a head, dt_bias the
    # inverse softplus of a step log-uniform in (0.001, 0.1) a channel.
    dt = jnp.exp(jax.random.uniform(
        w.key("dt_bias"), (q_width,), jnp.float32,
        math.log(0.001), math.log(0.1)))
    lyr.update(
        wb=w("wb", cfg.dim, heads), wo=w("wo", q_width, cfg.dim),
        A_log=jnp.log(jax.random.uniform(w.key("A_log"), (heads,),
                                         jnp.float32, 1.0, 16.0)),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        o_norm=unit_gain(cfg, cfg.head_dim))
    return lyr


def _pspecs(cfg, kind, tp, tp_size):
    if tp_size > 1:
        raise ValueError(
            f"linear_attention does not shard over 'tp' "
            f"(tp={tp_size}): no tp layout of the scan's heads "
            "is written")
    layer = {key: P(None, None)
             for key in ("wq", "wk", "wv", "wf", "wb", "wo", "conv_q",
                         "conv_k", "conv_v")}
    layer.update(A_log=P(None), dt_bias=P(None), o_norm=P(None))
    return layer


def _refuse(cfg, mesh):
    if mesh is None or mesh.size <= 1:
        return
    for axis, why in (("sp", "the scan's state is not handed from chip "
                             "to chip along an 'sp' ring"),
                      ("tp", "no tp layout of the scan's heads is "
                             "written"),
                      ("pp", "pipeline stages take every layer alike")):
        if int(mesh.shape.get(axis, 1)) > 1:
            raise ValueError(f"linear_attention does not run over "
                             f"{axis}={mesh.shape[axis]}: {why}")
    raise ValueError(
        f"linear_attention runs on one device: on a mesh of {mesh.size} "
        "the scan's Mosaic kernel would need a shard_map of its own "
        "(GSPMD cannot partition it)")


def _heads(ctx, kind, h, lyr):
    """Linear attention's heads from the normed input ``h``: flat,
    [B, T, heads * head_dim], normed a head (the module docstring has the
    layout)."""
    from ...ops.kda import kda       # pallas: imported where first traced

    cfg, wc, dt = ctx.cfg, ctx.wc, ctx.dt
    Bb, Tb, _ = h.shape
    local_heads = kind.heads // ctx.tp
    D, f32 = cfg.head_dim, jnp.float32
    taps = cfg.linear_conv_kernel
    metrics.counter("attention.linear_flat_traced",
                    {"heads": str(local_heads)}).inc()

    def conv(x, kernel):
        # causal, depthwise: tap j reads the token taps - 1 - j back
        kernel = kernel.astype(dt)
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        y = sum(padded[:, j:j + Tb] * kernel[j] for j in range(taps))
        return jax.nn.silu(y)

    def unit(x):        # L2-normalised a head, in float32
        x = x.astype(f32)
        return x * head_spread(jax.lax.rsqrt(
            head_sum(jnp.square(x), local_heads) + 1e-6), D)

    def split(x):       # a bitcast pair with kda's own flatten
        return x.reshape(Bb, Tb, local_heads, D)

    with proj():
        q = h @ wc(lyr["wq"])
    with elem():
        q = (unit(conv(q, lyr["conv_q"])) * D ** -0.5).astype(dt)
    with proj():
        k = h @ wc(lyr["wk"])
    with elem():
        k = unit(conv(k, lyr["conv_k"])).astype(dt)
    with proj():
        v = h @ wc(lyr["wv"])
    with elem():
        v = conv(v, lyr["conv_v"])
    with proj():
        f = jnp.dot(h, wc(lyr["wf"]), preferred_element_type=f32)
    with elem():
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.repeat(jnp.exp(lyr["A_log"].astype(f32)), D)
            * (f + lyr["dt_bias"].astype(f32)))
    with proj():
        beta = jnp.dot(h, wc(lyr["wb"]), preferred_element_type=f32)
    with elem():
        beta = jax.nn.sigmoid(beta)
        q, k, v, g = split(q), split(k), split(v), split(g)
    o = kda(q, k, v, g, beta)
    with elem():
        o = o.reshape(Bb, Tb, local_heads * D)
        # rms_norm a head, its mean of squares taken flat
        var = head_sum(jnp.square(o.astype(f32)), local_heads) / D
        return ((o * head_spread(jax.lax.rsqrt(var + cfg.norm_eps), D)
                 ).astype(o.dtype)
                * jnp.tile(ctx.gain(lyr["o_norm"]), local_heads))


LINEAR = AttnKind(
    scope="attn.linear", saved=("kda_out", "kda_state", "kda_solve"),
    gate_tp=False, check=_check, init=_init, pspecs=_pspecs, refuse=_refuse,
    rope=lambda cfg: cfg.rope_full, heads=_heads)
