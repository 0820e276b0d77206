"""``latent_attention`` (arXiv:2405.04434, decompressed form): ``c_q =
norm(h wq_a)`` [q_lora_rank], ``q = c_q wq_b`` [heads, qk_nope_dim +
qk_rope_dim], or with ``q_lora_rank = 0`` no query latent: ``q = h wq``;
``h wkv_a`` = ``c_kv`` [kv_lora_rank] and one rotated key head [qk_rope_dim];
``norm(c_kv) wkv_b`` [heads, qk_nope_dim + v_head_dim].  A score adds the
rotated part, its key one head for all, to the unrotated one (kernels
``flash_mla_*`` of ``ops/flash_attention.py``).  The rotated parts take
``rope_latent``; the softmax scale is ``(qk_nope_dim + qk_rope_dim) ** -0.5 *
attn_mscale ** 2`` (YaRN's mscale on q and k both).  One device: no layout
over ``tp`` or ``sp`` is written."""

from __future__ import annotations

from jax.sharding import PartitionSpec as P

from ...parallel.ring_attention import blockwise_attention_local
from ..common import (AttnKind, apply_rope, elem, proj, rms_norm,
                      unit_gain)

__all__ = ["LATENT"]


def _check(cfg, kind):
    widths = ("kv_lora_rank", "qk_nope_dim", "qk_rope_dim", "v_head_dim")
    if (not all(getattr(cfg, w) > 0 for w in widths)
            or cfg.q_lora_rank < 0):
        raise ValueError(
            f"latent_attention layers need {widths} > 0 and "
            "q_lora_rank >= 0 (0 = no query latent: q = h wq)")
    if cfg.n_kv_heads or cfg.qk_norm:
        raise ValueError(
            "latent_attention layers take no n_kv_heads or "
            "qk_norm: their K/V are per head and their norms "
            "are the latent ones")


def _init(cfg, kind, w):
    heads = kind.heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    lyr = ({"wq_a": w("wq_a", cfg.dim, cfg.q_lora_rank),
            "q_a_norm": unit_gain(cfg, cfg.q_lora_rank),
            "wq_b": w("wq_b", cfg.q_lora_rank, heads * (dn + dr))}
           if cfg.q_lora_rank
           else {"wq": w("wq", cfg.dim, heads * (dn + dr))})
    lyr.update({
        "wkv_a": w("wkv_a", cfg.dim, cfg.kv_lora_rank + dr),
        "kv_a_norm": unit_gain(cfg, cfg.kv_lora_rank),
        "wkv_b": w("wkv_b", cfg.kv_lora_rank, heads * (dn + dv)),
        "wo": w("wo", heads * dv, cfg.dim),
    })
    return lyr


def _pspecs(cfg, kind, tp, tp_size):
    if tp_size > 1:
        raise ValueError(
            f"latent_attention does not shard over 'tp' "
            f"(tp={tp_size}): the heads leave wq_b/wkv_b "
            "interleaved with the low-rank norms' inputs whole, and no "
            "tp layout of the latent projections is written")
    layer = ({"wq_a": P(None, None), "q_a_norm": P(None),
              "wq_b": P(None, None)} if cfg.q_lora_rank
             else {"wq": P(None, None)})
    layer.update({"wkv_a": P(None, None), "kv_a_norm": P(None),
                  "wkv_b": P(None, None), "wo": P(None, None)})
    return layer


def _refuse(cfg, mesh):
    if mesh is None or mesh.size <= 1:
        return
    for axis, why in (("sp", "its two-part scores do not ride the 'sp' "
                             "ring"),
                      ("tp", "no tp layout of the latent projections "
                             "is written")):
        if int(mesh.shape.get(axis, 1)) > 1:
            raise ValueError(f"latent_attention does not run over "
                             f"{axis}={mesh.shape[axis]}: {why}")
    raise ValueError(
        f"latent_attention runs on one device: on a mesh of {mesh.size} "
        "the Mosaic kernel sits inside ring_attention's shard_map, "
        "which carries one width for q, k and v")


def _heads(ctx, kind, h, lyr):
    """Latent attention's heads from the normed input ``h``:
    [B, T, heads, v_head_dim]."""
    cfg, wc = ctx.cfg, ctx.wc
    Bb, Tb, _ = h.shape
    local_heads = kind.heads // ctx.tp
    rope = cfg.rope(kind.attn)
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    scale = (dn + dr) ** -0.5 * cfg.attn_mscale ** 2
    if cfg.q_lora_rank:
        with proj():
            c_q = h @ wc(lyr["wq_a"])
        with elem():
            c_q = rms_norm(c_q, ctx.gain(lyr["q_a_norm"]), cfg.norm_eps)
        with proj():
            q = c_q @ wc(lyr["wq_b"])
    else:
        with proj():
            q = h @ wc(lyr["wq"])
    with elem():
        q = q.reshape(Bb, Tb, local_heads, dn + dr).transpose(0, 2, 1, 3)
    with proj():
        kv_a = h @ wc(lyr["wkv_a"])
    with elem():
        c_kv = rms_norm(kv_a[..., :cfg.kv_lora_rank],
                        ctx.gain(lyr["kv_a_norm"]), cfg.norm_eps)
    with proj():
        kv = c_kv @ wc(lyr["wkv_b"])
    with elem():
        kv = kv.reshape(Bb, Tb, local_heads, dn + dv).transpose(0, 2, 1, 3)
        # the rotated key part is one head, whatever the query heads
        k_r = apply_rope(kv_a[..., cfg.kv_lora_rank:][:, None], rope)
        q_n, k_n, v = q[..., :dn], kv[..., :dn], kv[..., dn:]
        q_r = apply_rope(q[..., dn:], rope)
    o = blockwise_attention_local(q_n, k_n, v, scale, causal=True,
                                  q_rope=q_r, k_rope=k_r)
    with elem():
        return o.transpose(0, 2, 1, 3)                       # [B,T,H,dv]


LATENT = AttnKind(
    scope="attn.latent", saved=("flash_out", "flash_lse"), gate_tp=False,
    check=_check, init=_init, pspecs=_pspecs, refuse=_refuse,
    rope=lambda cfg: cfg.rope_latent, heads=_heads)
