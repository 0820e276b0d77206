"""``eva_attention`` (EVA, arXiv:2302.04542, as EvaByte runs it;
``ops/flash_eva.py``), ``heads`` heads of ``head_dim``: q, k rotated
(``rope_full``), v plain; chunk ``m`` of ``eva_chunk`` positions is one key
and one value, ``a_j = softmax_{j in m}(scale k_j . phi)``, ``kbar_m = sum_j
a_j k_j + mu``, ``vbar_m = sum_j a_j v_j`` (leaves ``phi``, ``mu`` [heads,
head_dim]); query t of window ``w = t // eva_window`` sees the keys
``eva_window * w <= j <= t`` and the summaries ``m < (eva_window //
eva_chunk) * w`` in ONE softmax.  One device."""

from __future__ import annotations

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..common import AttnKind, apply_rope, elem, proj

__all__ = ["EVA"]


def _check(cfg, kind):
    if cfg.n_kv_heads or cfg.qk_norm:
        raise ValueError(
            "eva_attention layers take no n_kv_heads or qk_norm: "
            "every head pools its own keys and values")
    if (cfg.eva_chunk < 1 or cfg.eva_window < cfg.eva_chunk
            or cfg.eva_window % cfg.eva_chunk):
        raise ValueError(
            "eva_attention layers need eva_window a multiple of "
            f"eva_chunk >= 1, got {cfg.eva_window} / "
            f"{cfg.eva_chunk}")


def _init(cfg, kind, w):
    heads, width = kind.heads, kind.heads * cfg.head_dim
    lyr = {
        "wq": w("wq", cfg.dim, width),
        "wk": w("wk", cfg.dim, width),
        "wv": w("wv", cfg.dim, width),
        "wo": w("wo", width, cfg.dim),
    }
    # The pooling's query and the summaries' key offset, a head:
    # N(0, 1 / head_dim), cut at three deviations.
    std = cfg.head_dim ** -0.5
    lyr.update({name: jnp.clip(
        w.normal(name, (heads, cfg.head_dim), std), -3 * std, 3 * std)
        for name in ("phi", "mu")})
    return lyr


def _pspecs(cfg, kind, tp, tp_size):          # one device (``_refuse``)
    return {key: P(None, None)
            for key in ("wq", "wk", "wv", "wo", "phi", "mu")}


def _refuse(cfg, mesh):
    if mesh is not None and mesh.size > 1:
        raise ValueError(
            f"eva_attention runs on one device: on a mesh of {mesh.size} "
            f"({dict(mesh.shape)}) its Mosaic kernels would need a shard_map "
            "of their own (GSPMD cannot partition them), and no layout of "
            "its heads and their summaries over 'tp', or of a window's "
            "summaries along an 'sp' ring, is written")


def _heads(ctx, kind, h, lyr):
    """EVA attention's heads from the normed input ``h``:
    [B, T, heads, head_dim]."""
    from ...ops.flash_eva import eva_attention, summarise   # pallas, as kda

    cfg, wc = ctx.cfg, ctx.wc
    Bb, Tb, _ = h.shape
    local_heads = kind.heads // ctx.tp
    D, scale = cfg.head_dim, cfg.head_dim ** -0.5
    rope = cfg.rope(kind.attn)

    def heads_of(y):
        return y.reshape(Bb, Tb, local_heads, D).transpose(0, 2, 1, 3)

    with proj():
        q = h @ wc(lyr["wq"])
    with elem():
        q = apply_rope(heads_of(q), rope)
    with proj():
        k = h @ wc(lyr["wk"])
    with elem():
        k = apply_rope(heads_of(k), rope)
    with proj():
        v = h @ wc(lyr["wv"])
    chunk = cfg.eva_chunk
    # whole chunks, and past one window whole windows: the padding
    # lies after every query, so none sees it or its summaries
    pad = -Tb % (cfg.eva_window if Tb > cfg.eva_window else chunk)
    with elem():
        v = heads_of(v)
        if pad:
            q, k, v = (jnp.pad(y, ((0, 0), (0, 0), (0, pad), (0, 0)))
                       for y in (q, k, v))
    kbar, vbar = summarise(k, v, lyr["phi"], lyr["mu"], scale, chunk)
    o = eva_attention(q, k, v, kbar, vbar, cfg.eva_window, chunk,
                      scale=scale)
    with elem():
        return o[:, :, :Tb].transpose(0, 2, 1, 3)            # [B,T,H,D]


EVA = AttnKind(
    scope="attn.eva", saved=("flash_out", "flash_lse"), gate_tp=False,
    check=_check, init=_init, pspecs=_pspecs, refuse=_refuse,
    rope=lambda cfg: cfg.rope_full, heads=_heads)
