"""``full_attention`` and ``sliding_attention``: causal softmax attention of
``heads`` rotated query heads over grouped K/V heads (``n_kv_heads``), a
sliding layer seeing the keys ``t - sliding_window < s <= t`` alone; and
``full_attention_nope``, full attention whose queries and keys carry no
position embedding at all (the causal mask is all that orders the keys): it
has no recipe and traces no angle, no ``cos`` and no ``sin``, counted in
``attention.nope_traced{heads=}``.  The only kinds with a layout over ``tp``
(heads shard) and, full layers, ``sp`` (``parallel/ring_attention.py``)."""

from __future__ import annotations

from jax.sharding import PartitionSpec as P

from ... import metrics
from ...parallel.ring_attention import (blockwise_attention_local,
                                        ring_attention)
from ..common import AttnKind, apply_rope, elem, proj, rms_norm

__all__ = ["FULL", "SLIDING", "NOPE"]


def _check(cfg, kind):
    kv = cfg.n_kv_heads
    if kv and kind.heads % kv:
        raise ValueError(f"{kind.heads} query heads do not divide into "
                         f"{kv} K/V heads")


def _check_sliding(cfg, kind):
    _check(cfg, kind)
    if cfg.sliding_window < 1:
        raise ValueError("sliding_attention layers need "
                         "sliding_window >= 1")


def _init(cfg, kind, w):
    q_width = kind.heads * cfg.head_dim
    kv_width = (cfg.n_kv_heads or kind.heads) * cfg.head_dim
    return {
        "wq": w("wq", cfg.dim, q_width),
        "wk": w("wk", cfg.dim, kv_width),
        "wv": w("wv", cfg.dim, kv_width),
        "wo": w("wo", q_width, cfg.dim),
    }


def _pspecs(cfg, kind, tp, tp_size):
    return {"wq": P(None, tp), "wk": P(None, tp), "wv": P(None, tp),
            "wo": P(tp, None)}


def _refuse_sliding(cfg, mesh):
    if mesh is not None and int(mesh.shape.get("sp", 1)) > 1:
        raise ValueError(
            f"sliding_attention layers (window {cfg.sliding_window}) do "
            f"not run over an 'sp' ring (sp={mesh.shape['sp']})")


def _heads(ctx, kind, h, lyr, window=None, rotate=True):
    cfg, wc = ctx.cfg, ctx.wc
    Bb, Tb, _ = h.shape
    local_heads = kind.heads // ctx.tp
    local_kv = (cfg.n_kv_heads or kind.heads) // ctx.tp
    scale = cfg.head_dim ** -0.5
    rope = cfg.rope(kind.attn) if rotate else None

    def turn(t):            # [B,H,T,D], rotated where the kind has a recipe
        t = t.transpose(0, 2, 1, 3)
        return t if rope is None else apply_rope(t, rope)

    with proj():
        q, k = h @ wc(lyr["wq"]), h @ wc(lyr["wk"])
    with elem():
        if cfg.qk_norm:
            q = rms_norm(q, ctx.gain(lyr["q_norm"]), cfg.norm_eps)
            k = rms_norm(k, ctx.gain(lyr["k_norm"]), cfg.norm_eps)
        q = q.reshape(Bb, Tb, local_heads, cfg.head_dim)
        k = k.reshape(Bb, Tb, local_kv, cfg.head_dim)
    with proj():
        v = h @ wc(lyr["wv"])
    with elem():
        v = v.reshape(Bb, Tb, local_kv, cfg.head_dim)
        q, k, v = turn(q), turn(k), v.transpose(0, 2, 1, 3)
    if ctx.ring:
        o = ring_attention(q, k, v, ctx.mesh, axis_name="sp", causal=True,
                           scale=scale, window=window)
    else:
        o = blockwise_attention_local(q, k, v, scale, causal=True,
                                      window=window)
    with elem():
        return o.transpose(0, 2, 1, 3)                       # [B,T,H,D]


def _heads_sliding(ctx, kind, h, lyr):
    return _heads(ctx, kind, h, lyr, window=ctx.cfg.sliding_window)


def _heads_nope(ctx, kind, h, lyr):
    metrics.counter("attention.nope_traced", {"heads": str(kind.heads)}).inc()
    return _heads(ctx, kind, h, lyr, rotate=False)


FULL = AttnKind(
    scope="attn.full", saved=("flash_out", "flash_lse"), gate_tp=True,
    check=_check, init=_init, pspecs=_pspecs,
    refuse=lambda cfg, mesh: None, rope=lambda cfg: cfg.rope_full,
    heads=_heads)
SLIDING = FULL._replace(
    scope="attn.sliding", check=_check_sliding, refuse=_refuse_sliding,
    rope=lambda cfg: cfg.rope_sliding, heads=_heads_sliding)
NOPE = FULL._replace(scope="attn.full_nope", rope=lambda cfg: None,
                     heads=_heads_nope)
