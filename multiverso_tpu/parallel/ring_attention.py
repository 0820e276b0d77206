"""Ring attention — sequence/context parallelism over a mesh axis.

The reference framework predates long-context models and has nothing here
(SURVEY.md §5 "long-context: does not exist"), but this framework treats
long-context as first-class: sequences shard over a mesh axis (``sp``) and
attention runs blockwise, rotating K/V shards around the ring with
``ppermute`` over ICI while each device accumulates its queries' output
with an online (streaming) softmax.  Peak memory per device is O(T_local²)
instead of O(T_global²), and the K/V transfer overlaps compute around the
ring — the standard TPU recipe for million-token contexts.

Implementation: ``shard_map`` + ``lax.fori_loop`` + ``ppermute`` with
static shapes; each ring step computes a normalized ``(o, lse)`` piece —
on TPU via the differentiable Pallas flash kernel
(``ops/flash_attention.py``), elsewhere via the fused jnp streaming
path — and pieces combine with the logsumexp identity.  XLA overlaps the
collective-permute with the block compute on TPU.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import metrics
from ..log import Log

__all__ = ["ring_attention", "blockwise_attention_local"]

_NEG = -1e30  # finite mask sentinel: exp(_NEG - m) underflows to exactly 0


def _online_block(q, k_blk, v_blk, o, m, l, q_pos, k_pos, scale, causal,
                  window=None, rope=None):
    """One streaming-softmax accumulation step over a K/V block.

    q [B,H,T,D]; k_blk/v_blk [B,KV,Tb,D], KV dividing H (query head j reads
    K/V head ``j // (H // KV)``: the heads are repeated here, this being the
    path off the chip); o [B,H,T,D] f32; m,l [B,H,T,1] f32; q_pos [T], k_pos
    [Tb] are GLOBAL positions for causal masking, which a ``window`` narrows
    to the keys ``t - window < s <= t``.
    The block matmul runs in the compute dtype (MXU); the softmax
    statistics and the output accumulate in float32 — bf16 accumulation
    across ring steps would compound rounding error.
    ``rope = (q_rope [B,H,T,Dr], k_rope [B,1,Tb,Dr])`` adds a second product
    to the scores, its key one head for all H (latent attention).
    """
    group = q.shape[1] // k_blk.shape[1]
    if group > 1:
        k_blk = jnp.repeat(k_blk, group, axis=1)
        v_blk = jnp.repeat(v_blk, group, axis=1)
    s = jnp.einsum("bhtd,bhsd->bhts", q, k_blk).astype(jnp.float32) * scale
    if rope is not None:
        s = s + jnp.einsum("bhtd,bsd->bhts", rope[0], rope[1][:, 0]
                           ).astype(jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]                # [T,Tb]
        if window is not None:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, None], s, _NEG)
    blk_max = jnp.max(s, axis=-1, keepdims=True)               # [B,H,T,1]
    new_m = jnp.maximum(m, blk_max)
    # exp(_NEG - new_m) == 0 for every masked entry once any real score
    # has been seen; before that the correction factor zeroes the garbage.
    p = jnp.exp(s - new_m)
    corr = jnp.exp(m - new_m)
    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    o = o * corr + jnp.einsum("bhts,bhsd->bhtd",
                              p.astype(v_blk.dtype), v_blk
                              ).astype(jnp.float32)
    return o, new_m, l


def _flash_block(t: int, cap: int, head_dim: int) -> int:
    """Block for the flash dispatch: the kernel's own fit policy
    (``ops.flash_attention.fit_block``) gated at ≥64 — below that the
    non-pallas scan path wins (0 = don't dispatch flash).

    Caps are the measured v5e sweet spot at D=128: q blocks 512, k
    blocks 1024 (``ops/flash_attention.py`` docstring).  The kernel's
    VMEM footprint scales with block·head_dim (k/v tiles) — larger head
    dims shrink the cap proportionally so D=256 keeps the D=128 budget
    instead of risking Mosaic VMEM exhaustion."""
    from ..ops.flash_attention import fit_block, scale_cap_for_head_dim

    b = fit_block(scale_cap_for_head_dim(cap, head_dim), t)
    return b if b >= 64 else 0


def _flash_dispatch(tq: int, tk: int, head_dim: int,
                    window: Optional[int] = None
                    ) -> Optional[Tuple[int, int, bool]]:
    """``(block_q, block_k, interpret)`` for the Pallas flash kernel, or
    ``None`` for the jnp streaming path.

    Where the flash kernels' body is decided: ``ops.kernel_path()`` (the
    backend and the two switches, shared with the other kernels) and whether
    blocks fit.  It is taken
    at trace time, so a compiled step holds whichever body this returned
    and never switches; every decision is therefore counted in
    ``attention.traced{path=mosaic|interpret|jnp}`` (``metrics``), and a
    TPU trace that lands on the O(T²) jnp body is logged with its shapes.
    A windowed trace is counted in ``attention.window_traced{window=}`` as
    well, whichever body it got.

    - TPU backend, blocks fit: the compiled Mosaic kernel.
    - ``MVTPU_FORCE_FLASH`` (any non-empty value) off-TPU: the same
      kernel in interpret mode, so CI covers this exact dispatch.
    - ``MVTPU_NO_FLASH``, or no block ≥64 divides the sequence: jnp.
    """
    from ..ops.kernel_path import kernel_path

    bq = _flash_block(tq, cap=512, head_dim=head_dim)
    bk = _flash_block(tk, cap=1024, head_dim=head_dim)
    path = kernel_path() if bq and bk else "jnp"
    if path == "jnp" and jax.default_backend() == "tpu":
        Log.info("attention Tq=%d Tk=%d D=%d traced on the O(T^2) jnp "
                 "path (flash blocks %d/%d, MVTPU_NO_FLASH=%r)",
                 tq, tk, head_dim, bq, bk,
                 os.environ.get("MVTPU_NO_FLASH", ""))
    metrics.counter("attention.traced", {"path": path}).inc()
    if window is not None:
        metrics.counter("attention.window_traced",
                        {"window": str(window)}).inc()
    return None if path == "jnp" else (bq, bk, path == "interpret")


def blockwise_attention_local(q, k, v, scale: float, causal: bool = True,
                              q_offset: int = 0, k_offset: int = 0,
                              window: Optional[int] = None,
                              q_rope=None, k_rope=None):
    """Single-device attention (the ring's degenerate case).  q [B,H,T,D];
    k/v [B,KV,T,D] with KV dividing H (grouped K/V heads); ``window`` keeps
    the keys ``t - window < s <= t``.  ``q_rope [B,H,T,Dr]`` and ``k_rope
    [B,1,T,Dr]`` (latent attention: causal, aligned, no window) add ``q_rope
    . k_rope`` to every score, and ``v`` may then be another width than
    ``q``; the kernel is ``flash_attention_latent``.

    Aligned shapes dispatch to the Pallas flash kernel
    (``ops/flash_attention.py``) — O(T) memory, causal-block skipping,
    differentiable via its custom_vjp — as ``_flash_dispatch`` decides;
    offset blocks and whatever it declines run the jnp streaming-softmax
    path, which XLA fuses.
    """
    B, H, T, D = q.shape
    latent = q_rope is not None
    if latent and (not causal or window is not None or q_offset or k_offset
                   or T != k.shape[2]):
        raise ValueError("a rotated score part (q_rope, k_rope) needs "
                         "causal, aligned attention without a window")
    flash = None
    if q_offset == 0 and k_offset == 0 and T == k.shape[2]:
        # Latent attention keeps the D=128 blocks: no operand's tile is
        # wider than 128 (the rotated parts are 64), and the v5e compiler
        # takes both kernels at 512x1024 / 1024x1024.
        flash = _flash_dispatch(T, T, max(D, v.shape[-1]) if latent else D,
                                window)
    if flash is not None and latent:
        from ..ops.flash_attention import flash_attention_latent

        bq, bk, interpret = flash
        return flash_attention_latent(q, q_rope, k, k_rope, v, scale=scale,
                                      block_q=bq, block_k=bk,
                                      interpret=interpret)
    if flash is not None:
        from ..ops import flash_attention

        bq, bk, interpret = flash
        return flash_attention(q, k, v, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, interpret=interpret,
                               window=window)
    o = jnp.zeros(q.shape[:3] + v.shape[-1:], jnp.float32)
    m = jnp.full((B, H, T, 1), _NEG, jnp.float32)
    l = jnp.zeros((B, H, T, 1), jnp.float32)
    q_pos = q_offset + jnp.arange(T)
    k_pos = k_offset + jnp.arange(k.shape[2])
    o, m, l = _online_block(q, k, v, o, m, l, q_pos, k_pos, scale, causal,
                            window, (q_rope, k_rope) if latent else None)
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def _attn_piece(q, k, v, scale, causal: bool):
    """Normalized attention over one K/V block, plus row logsumexp.

    Returns ``(o [B,H,Tq,D] in q.dtype, lse [B,H,Tq] float32)``.  Pieces
    compose across ring steps: ``lse' = logaddexp(lse1, lse2); o' =
    o1·e^{lse1-lse'} + o2·e^{lse2-lse'}`` — so each ring step can run the
    Pallas flash kernel at full kernel speed and the combination stays
    pure jnp (fused by XLA).  Where ``_flash_dispatch`` declines, the jnp
    streaming path computes the same pair.  ``causal=True`` requires
    Tq == Tk (aligned diagonal), matching the kernel's contract.
    """
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    flash = _flash_dispatch(Tq, Tk, D)
    if flash is not None:
        from ..ops import flash_attention

        bq, bk, interpret = flash
        return flash_attention(q, k, v, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, interpret=interpret,
                               return_lse=True)
    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((B, H, Tq, 1), _NEG, jnp.float32)
    l = jnp.zeros((B, H, Tq, 1), jnp.float32)
    o, m, l = _online_block(q, k, v, o, m, l, jnp.arange(Tq),
                            jnp.arange(Tk), scale, causal)
    lse = (m + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype), lse


def _combine_pieces(o_acc, lse_acc, o_i, lse_i):
    """Fold one (o, lse) piece into the float32 accumulators."""
    new_lse = jnp.logaddexp(lse_acc, lse_i)
    o_acc = (o_acc * jnp.exp(lse_acc - new_lse)[..., None]
             + o_i.astype(jnp.float32) * jnp.exp(lse_i - new_lse)[..., None])
    return o_acc, new_lse


def _empty_piece(q):
    """A contributes-nothing piece (fully masked ring step)."""
    return (jnp.zeros(q.shape, q.dtype),
            jnp.full(q.shape[:3], _NEG, jnp.float32))


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = True,
                   batch_axis: Optional[str] = "dp",
                   head_axis: Optional[str] = "tp",
                   scale: Optional[float] = None,
                   layout: str = "auto",
                   window: Optional[int] = None):
    """Causal self-attention with sequences sharded over ``axis_name``.

    ``q``/``k``/``v``: [B, H, T_global, D] jax.Arrays (sharded or not —
    shard_map re-lays them: batch over ``batch_axis``, heads over
    ``head_axis``, sequence over ``axis_name``).  Returns [B, H, T, D]
    with the same layout.  The streaming softmax accumulates statistics and
    output in float32 regardless of the compute dtype, so bf16 inputs see
    only the block-matmul rounding, not compounded per-ring-step error.

    ``layout``: ``"contiguous"`` gives each device one contiguous sequence
    block — simple, but under causal masking low-rank devices burn most
    ring steps on fully-masked blocks.  ``"zigzag"`` gives each device the
    chunk pair (d, 2*sp-1-d), which balances causal work exactly: every
    non-self ring step computes two fully-unmasked c x c sub-blocks — half
    the FLOPs of the contiguous schedule — at the cost of one global
    sequence permutation on the way in and out.  ``"auto"`` picks zigzag
    for causal attention whenever 2*sp divides T.

    ``k``/``v`` may hold fewer heads than ``q`` (grouped K/V heads); the
    head axis then has to divide them too.  ``window`` (keys ``t - window <
    s <= t``) runs wherever the sequence is not sharded; a ring over ``sp``
    with a window is refused.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mesh.size == 1:
        return blockwise_attention_local(q, k, v, scale, causal,
                                         window=window)
    # Every multi-device mesh goes through shard_map (all axes manual),
    # ring or not: a Mosaic kernel under plain jit on >1 device fails to
    # lower ("cannot be automatically partitioned").
    axes = dict(mesh.shape)
    sp = int(axes.get(axis_name, 1))
    if window is not None and sp > 1:
        raise ValueError(
            f"ring attention over '{axis_name}' ({sp}) with a sliding "
            f"window ({window}) is unsupported: the ring's steps know full "
            "and causal blocks only")
    b_ax = batch_axis if (batch_axis and batch_axis in axes) else None
    if b_ax and q.shape[0] % axes[b_ax]:
        Log.info("ring_attention: batch %d does not divide mesh axis '%s' "
                 "(%d): attention REPLICATED over it, not sharded",
                 q.shape[0], b_ax, axes[b_ax])
        b_ax = None
    h_ax = head_axis if (head_axis and head_axis in axes) else None
    if h_ax and k.shape[1] % axes[h_ax]:
        raise ValueError(
            f"{k.shape[1]} K/V heads do not divide over mesh axis '{h_ax}' "
            f"({axes[h_ax]})")
    spec = P(b_ax, h_ax, axis_name if sp > 1 else None, None)

    if layout not in ("auto", "zigzag", "contiguous"):
        raise ValueError(
            f"unknown layout '{layout}'; expected auto|zigzag|contiguous")
    T_global = q.shape[2]
    use_zigzag = (sp > 1 and causal and T_global % (2 * sp) == 0
                  and layout in ("auto", "zigzag"))
    if layout == "zigzag" and not use_zigzag:
        raise ValueError(
            f"zigzag layout needs sp > 1 (got {sp}), causal=True (got "
            f"{causal}), and T ({T_global}) divisible by 2*sp ({2 * sp})")

    if use_zigzag:
        c = T_global // (2 * sp)
        perm = np.concatenate(
            [np.r_[d * c:(d + 1) * c,
                   (2 * sp - 1 - d) * c:(2 * sp - d) * c]
             for d in range(sp)])
        inv_perm = np.argsort(perm)
        q = jnp.take(q, perm, axis=2)
        k = jnp.take(k, perm, axis=2)
        v = jnp.take(v, perm, axis=2)

    def local_contiguous(q_l, k_l, v_l):
        B, H, T, D = q_l.shape
        if sp == 1:
            return blockwise_attention_local(q_l, k_l, v_l, scale, causal,
                                             window=window)
        idx = jax.lax.axis_index(axis_name)
        o_acc = jnp.zeros(q_l.shape, jnp.float32)
        lse_acc = jnp.full((B, H, T), _NEG, jnp.float32)
        ring = [(j, (j + 1) % sp) for j in range(sp)]

        def body(i, carry):
            o_acc, lse_acc, k_blk, v_blk = carry
            src = (idx - i) % sp          # owner of the current K/V block
            if causal:
                # src == idx: aligned diagonal (causal kernel); src < idx:
                # every position valid (full kernel); src > idx: fully
                # masked — skip the matmuls entirely.
                o_i, lse_i = jax.lax.cond(
                    src == idx,
                    lambda kv: _attn_piece(q_l, kv[0], kv[1], scale, True),
                    lambda kv: jax.lax.cond(
                        src < idx,
                        lambda kv2: _attn_piece(q_l, kv2[0], kv2[1],
                                                scale, False),
                        lambda kv2: _empty_piece(q_l),
                        kv),
                    (k_blk, v_blk))
            else:
                o_i, lse_i = _attn_piece(q_l, k_blk, v_blk, scale, False)
            o_acc, lse_acc = _combine_pieces(o_acc, lse_acc, o_i, lse_i)
            # rotate AFTER consuming; the last rotation is harmless and
            # keeps the loop body uniform (XLA overlaps it with compute)
            k_blk = jax.lax.ppermute(k_blk, axis_name, ring)
            v_blk = jax.lax.ppermute(v_blk, axis_name, ring)
            return o_acc, lse_acc, k_blk, v_blk

        o_acc, lse_acc, _, _ = jax.lax.fori_loop(
            0, sp, body, (o_acc, lse_acc, k_l, v_l))
        return o_acc.astype(q_l.dtype)

    def local_zigzag(q_l, k_l, v_l):
        B, H, T, D = q_l.shape                      # T == 2c
        idx = jax.lax.axis_index(axis_name)
        o_acc = jnp.zeros(q_l.shape, jnp.float32)
        lse_acc = jnp.full((B, H, T), _NEG, jnp.float32)
        ring = [(j, (j + 1) % sp) for j in range(sp)]

        def self_step(k_blk, v_blk):
            # Own chunk pair (low, high): low attends k_low causally;
            # high attends k_low fully and k_high causally — three
            # aligned kernel pieces, no bespoke mask.
            ql, qh = q_l[:, :, :c], q_l[:, :, c:]
            kl, kh = k_blk[:, :, :c], k_blk[:, :, c:]
            vl, vh = v_blk[:, :, :c], v_blk[:, :, c:]
            o_lo, lse_lo = _attn_piece(ql, kl, vl, scale, True)
            o_h1, lse_h1 = _attn_piece(qh, kl, vl, scale, False)
            o_h2, lse_h2 = _attn_piece(qh, kh, vh, scale, True)
            o_hi, lse_hi = _combine_pieces(o_h1.astype(jnp.float32),
                                           lse_h1, o_h2, lse_h2)
            return (jnp.concatenate([o_lo.astype(jnp.float32), o_hi], 2)
                    .astype(q_l.dtype),
                    jnp.concatenate([lse_lo, lse_hi], axis=2))

        def low_step(k_blk, v_blk):
            # src < idx: BOTH local chunks attend to src's LOW chunk only;
            # every score is valid — no mask, half the block FLOPs.
            return _attn_piece(q_l, k_blk[:, :, :c], v_blk[:, :, :c],
                               scale, False)

        def high_step(k_blk, v_blk):
            # src > idx: only the local HIGH chunk attends, to BOTH of
            # src's chunks; every score is valid — no mask.
            o_hi, lse_hi = _attn_piece(q_l[:, :, c:], k_blk, v_blk,
                                       scale, False)
            o_lo, lse_lo = _empty_piece(q_l[:, :, :c])
            return (jnp.concatenate([o_lo, o_hi], axis=2),
                    jnp.concatenate([lse_lo, lse_hi], axis=2))

        def body(i, carry):
            o_acc, lse_acc, k_blk, v_blk = carry
            src = (idx - i) % sp
            o_i, lse_i = jax.lax.cond(
                i == 0,
                lambda kv: self_step(*kv),
                lambda kv: jax.lax.cond(
                    src < idx,
                    lambda kv2: low_step(*kv2),
                    lambda kv2: high_step(*kv2),
                    kv),
                (k_blk, v_blk))
            o_acc, lse_acc = _combine_pieces(o_acc, lse_acc, o_i, lse_i)
            k_blk = jax.lax.ppermute(k_blk, axis_name, ring)
            v_blk = jax.lax.ppermute(v_blk, axis_name, ring)
            return o_acc, lse_acc, k_blk, v_blk

        o_acc, lse_acc, _, _ = jax.lax.fori_loop(
            0, sp, body, (o_acc, lse_acc, k_l, v_l))
        return o_acc.astype(q_l.dtype)

    local = local_zigzag if use_zigzag else local_contiguous
    out = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec, check_vma=False)(q, k, v)
    if use_zigzag:
        out = jnp.take(out, inv_perm, axis=2)
    return out
