"""Flash attention as a differentiable Pallas TPU kernel: causal or full,
with grouped K/V heads and a sliding window.

**Grouped K/V heads**: ``k``/``v`` may hold fewer heads than ``q``; query
head ``j`` reads K/V head ``j // group`` through the kernels' index maps
(nothing is repeated in HBM), and the backward's grid has an axis over the
group's query heads, so one K/V head's dk/dv stay in VMEM while all of them
pass (no ``[H, T, D]`` dk/dv summed afterwards).  **A window** keeps key
``s`` for query ``t`` iff ``t - window < s <= t``: the kernels' grids then
run over the blocks of that band alone (``_band_steps``), mask the two
partial diagonals, and are named ``flash_win_fwd`` and ``flash_win_bwd``
(``flash_win_bwd_dq``, ``flash_win_bwd_dkv`` where the backward is split).

**Two widths** (``flash_attention_latent``, latent attention's decompressed
form): a score is ``q_n . k_n`` over a head's unrotated dims plus ``q_r .
k_r`` over the rotated ones (two products, 128 + 64 wide: no operand is
padded to 256), values of a width of their own; causal only.  The rotated
key is ONE head that every query head reads through the index maps, its
gradient summed over them inside the kernel.  Kernels ``flash_mla_fwd`` and
ONE backward, ``flash_mla_bwd`` (PR 41: the tile built once, eight MXU
passes for the pair's eleven; ``flash_mla_bwd_dq`` + ``flash_mla_bwd_dkv``
where ``_mla_fused_fits`` turns the shape away); counters ``attention.
latent_traced{qk=,v=}``, ``attention.latent_bwd_traced{path=fused|split}``.

Causal/full attention with O(T) memory: the forward grid walks (batch·head,
q-block, k-block) with the k dimension innermost; per q-block the kernel
keeps the output accumulator and the streaming-softmax statistics (m, l)
in VMEM scratch across k-steps, writing the normalized output and the
row logsumexp once on the last step.  Score/accumulator math is float32
regardless of input dtype; the matmuls run on the MXU in the input dtype.
The causal schedule does half the FLOPs, which the XLA dense path cannot
do, and **a causal forward's grid holds a step only where there is a tile**
(PR 51; ``flash_fwd`` and ``flash_mla_fwd``): q block ``i`` and q block
``num_q - 1 - i`` compute ``num_k + 1`` k blocks between them, so the grid
is ``(batch·head, num_q / 2, num_k + 1)`` and row ``p`` walks q block
``p``'s k blocks and then q block ``num_q - 1 - p``'s (``_fold_step``:
arithmetic on the program ids in the index maps, no operand), 72 steps a
head for 128 at 8,192 tokens and 512 x 1024 blocks.  The shapes decide
(``_fwd_grid``; counter ``attention.fwd_traced{grid=folded|clamped|full|
band}``): a causal call the fold does not cover (``num_q`` odd or 1,
``block_q > block_k``) and the split backward's ``flash_bwd_dq`` keep a
grid over every k block, skip the blocks above the diagonal with
``pl.when`` and **clamp their K/V index to the row's last computed block**
(``_kv_index``), as the band's grids and the fused backwards do, so that no
block is fetched for a step that computes nothing.

Differentiation is a ``jax.custom_vjp``: the forward saves (q, k, v, o,
lse) and the backward rebuilds the probability blocks from lse instead of
materializing the T×T score matrix.  **One kernel a call** (``flash_bwd``,
PR 35) builds each tile's ``s``, mask, ``p = exp(s - lse)``, ``dp`` and
``ds = p (dp - delta)`` once and adds ``dv += p^T do``, ``dk += ds^T q``,
``dq += ds k``: five matmuls a tile.  A tile adds along a row of tiles (dq)
and along a column (dk, dv), so one of the two cannot be the block the inner
grid axis stays on: dk and dv are that block, and dq is held in VMEM for
the whole sequence of one query head, float32 ``[Tq, D]`` (4 MiB at 8192 x
128); with grouped K/V heads dk and dv are held whole as well, for the K/V
head while its query heads pass.  The tile is built transposed, ``[Bk,
Bq]``, so that dv and dk are plain matmuls and the row statistics (lse,
delta) arrive as rows ``[1, Bq]``.  Where those residents do not fit
(``_fused_fits``: one rule, from the shapes alone; 32,768 tokens at D=128
in bfloat16 is the longest that does, 10,922 with a group) the backward is
the two kernels it was: ``flash_bwd_dq`` accumulating
dq over k blocks and ``flash_bwd_dkv`` accumulating dk/dv over q blocks,
which between them build every tile twice and issue seven matmuls; their
per-row stats ride in lane-broadcast [*, T, 128] buffers.  Which ran is
counted at trace time in ``attention.bwd_traced{path=fused|split}``.

What bounds these kernels is the matmuls they issue, not the vector unit:
on a v5e at 1024 x 1024 x 128 tiles the dq kernel reads 76% of the MXU's
peak over its three, the dkv kernel 70% over its four and the fused one 89%
over its five (PERF.md section 6, PR 35).

The kernel also returns ``lse`` on request so sequence-parallel callers
can combine normalized partial results across ring steps: ``lse =
logaddexp(lse1, lse2); o = o1·e^{lse1-lse} + o2·e^{lse2-lse}`` (see
``parallel.ring_attention`` for the ring schedules; wiring the kernel
into the sp>1 ring steps uses exactly this identity).  The vjp accounts
for the lse cotangent by folding it
into the delta term (``ds = p·(dp − Δ)`` with ``Δ = rowsum(do·o) −
dlse``), so gradients flow correctly through that combination.

Used by ``parallel.ring_attention.blockwise_attention_local`` on TPU
backends; everywhere else the jnp fallback runs.  ``interpret=True`` runs
the same kernels on CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_path import elem

__all__ = ["flash_attention", "flash_attention_latent", "fit_block",
           "scale_cap_for_head_dim"]


def fit_block(block: int, t: int) -> int:
    """Largest power-of-two ≤ ``block`` dividing ``t`` (or ``t`` itself
    when ``t <= block``).  Blocks are a perf knob, not an API contract —
    requested sizes shrink to fit.  The one block-fitting policy for
    every flash dispatch site (the ring-attention dispatcher wraps this
    with its own floor)."""
    b = min(block, t)
    while b >= 8 and t % b:
        b //= 2
    return b


def scale_cap_for_head_dim(cap: int, head_dim: int) -> int:
    """VMEM guard shared by every dispatch site: block caps are measured
    at D=128, and the kernels' k/v tiles scale with block·head_dim — so
    larger head dims shrink the cap proportionally, rounded down to a
    power of two (``fit_block`` halves to find a divisor, so a non-pow2
    cap like D=192 → 341 would never land on one ≥64)."""
    if head_dim > 128:
        cap = max(64, cap * 128 // head_dim)
        cap = 1 << (cap.bit_length() - 1)
    return cap

_NEG = -1e30
_LANES = 128
_WINDOW_BLOCK_CAP = 512


def _causal_mask(s, qi, ki, block_q, block_k, window=None, q_axis=0):
    """Keep key s for query t iff ``s <= t`` and, with a window, ``t -
    window < s``.  A row of a band's first block may keep nothing: its
    ``m`` stays ``_NEG`` and what it accumulates is zeroed by ``corr`` when
    its first real score arrives (the diagonal is always kept).  The
    queries run along ``q_axis`` of ``s`` (1: a transposed tile, ``[Bk,
    Bq]``)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, q_axis)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1 - q_axis)
    visible = q_pos >= k_pos
    if window is not None:
        visible = visible & (k_pos > q_pos - window)
    return jnp.where(visible, s, _NEG)


# ---- the band ``t - window < s <= t`` in blocks.  A windowed call's grid
# runs over the blocks of the band alone: step j of q block i is k block
# ``_first_k(i) + j``, and the steps past ``_last_k(i)`` (the grid is as long
# as the longest row of blocks) are skipped with their index clamped, so
# that no block is fetched for them.  The dkv kernel walks the q blocks of
# a k block the same way.
def _first_k(qi, block_q, block_k, window):
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _last_k(qi, block_q, block_k):
    return (qi * block_q + block_q - 1) // block_k


def _first_q(ki, block_q, block_k):
    return (ki * block_k) // block_q


def _last_q(ki, block_q, block_k, window, num_q):
    return jnp.minimum((ki * block_k + block_k - 1 + window - 1) // block_q,
                       num_q - 1)


def _band_steps(num_q, num_k, block_q, block_k, window):
    """``(k steps a q block, q steps a k block)`` of the band's grids."""
    import numpy as np

    qi, ki = np.arange(num_q), np.arange(num_k)
    k_steps = (_last_k(qi, block_q, block_k)
               - np.maximum(qi * block_q - (window - 1), 0) // block_k + 1)
    q_steps = (np.minimum((ki * block_k + block_k + window - 2) // block_q,
                          num_q - 1) - _first_q(ki, block_q, block_k) + 1)
    return int(k_steps.max()), int(q_steps.max())


def _in_band(computed, full, qi, ki, block_q, block_k, window):
    """The causal ``(computed, full)`` of block (qi, ki) narrowed to the
    band: computed if its last key is inside the first query's window, full
    if its first key is inside the last query's."""
    q_lo, k_lo = qi * block_q, ki * block_k
    computed = computed & (k_lo + block_k - 1 > q_lo - window)
    full = full & (k_lo > q_lo + block_q - 1 - window)
    return computed, full


# ---- the causal forward's grid.  Without a window q block ``i`` computes the
# k blocks ``0 .. _last_k(i)``, a staircase: a grid over every k block would
# spend 44% of its steps at 8,192 tokens (512 x 1024 blocks) on blocks above
# the diagonal.  With ``r = block_k // block_q`` q block ``p`` computes ``p //
# r + 1`` k blocks and q block ``num_q - 1 - p`` computes ``num_k - p // r``:
# together ``num_k + 1``, whatever ``p``.  So the grid is FOLDED: ``(head,
# num_q / 2, num_k + 1)``, row ``p`` walking q block ``p``'s k blocks and
# then q block ``num_q - 1 - p``'s, each in ascending order, and every step
# has a tile.  The schedule is arithmetic on the program ids inside the index
# maps and the kernel (no prefetched table, no operand).
def _fwd_grid(causal, window, num_q, block_q, block_k):
    """Which grid a forward call runs, from its shapes alone: ``band`` (a
    window: the band's steps, clamped), ``folded`` (causal, ``num_q`` even,
    ``block_k`` a multiple of ``block_q``: a step only where there is a
    tile), ``clamped`` (any other causal call: every k block a q block, the
    steps past the diagonal skipped with their K/V index clamped to the
    row's last computed block, so that nothing is fetched for them) or
    ``full`` (not causal: every block is computed)."""
    if window is not None:
        return "band"
    if not causal:
        return "full"
    if num_q % 2 == 0 and block_k % block_q == 0:
        return "folded"
    return "clamped"


def _fold_step(p, j, num_q, r):
    """Step ``j`` of the folded grid's row ``p``: ``(q block, k block, first,
    last)``, ``first`` / ``last`` on the q block's first and last k block.
    Plain arithmetic, so that program ids and numpy arrays both pass."""
    short = p // r + 1                   # k blocks of q block p
    second = (j >= short) * 1            # 0 or 1: in the long half
    qi = p + second * (num_q - 1 - 2 * p)
    ki = j - second * short
    first = (j == 0) | (j == short)
    last = (j == short - 1) | (j == num_q // r)
    return qi, ki, first, last


def _fwd_step(fold, num_k):
    """``(q block, k step, first, last)`` of this grid step of a forward
    kernel: the folded schedule (``fold = (num_q, r)``), or ``(program_id(1),
    program_id(2))`` of a grid ``(head, q block, k step)``."""
    i, j = pl.program_id(1), pl.program_id(2)
    if fold is not None:
        return _fold_step(i, j, *fold)
    return i, j, j == 0, j == num_k - 1


def _causal_tile(compute, qi, ki, block_q, block_k, window=None,
                 every_step_computed=False):
    """Run ``compute(masked)`` for tile (qi, ki) of a causal forward.  Three
    block classes: strictly-above-diagonal blocks contribute nothing (skip:
    half the FLOPs); blocks fully below the diagonal need no mask (skip the
    iota/compare/select VPU passes); only diagonal-straddling blocks pay for
    masking.  The folded grid holds no block of the first class
    (``every_step_computed``), so two remain."""
    full = qi * block_q >= ki * block_k + block_k - 1
    if every_step_computed:
        pl.when(full)(lambda: compute(False))
        pl.when(jnp.logical_not(full))(lambda: compute(True))
        return
    computed = ki * block_k <= qi * block_q + block_q - 1
    if window is not None:
        computed, full = _in_band(computed, full, qi, ki, block_q, block_k,
                                  window)
    pl.when(computed & full)(lambda: compute(False))
    pl.when(computed & jnp.logical_not(full))(lambda: compute(True))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_scr, l_scr, *,
                causal, block_q, block_k, num_k, window=None, fold=None):
    # q arrives PRE-SCALED (softmax scale folded into the [T, D] input —
    # one multiply per q element instead of one per [Bq, Bk] score).
    # ``num_k`` is the grid's extent: every k block, or the band's steps;
    # ``fold = (num_q, r)`` where the grid is the folded one (``_fold_step``).
    qi, ki, first, last = _fwd_step(fold, num_k)
    if window is not None:
        ki = _first_k(qi, block_q, block_k, window) + ki

    @pl.when(first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    def _compute(masked):
        q = q_ref[0]                                   # [Bq, D]
        k = k_ref[0]                                   # [Bk, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Bq, Bk]
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k, window)
        m_prev = m_scr[:, 0:1]                          # [Bq, 1]
        l_prev = l_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_new

    if causal:
        _causal_tile(_compute, qi, ki, block_q, block_k, window,
                     every_step_computed=fold is not None)
    else:
        _compute(False)

    @pl.when(last)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0:1], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        # Lane-broadcast logsumexp; only lane 0 is meaningful downstream.
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, block_q, block_k, num_k,
               window=None):
    # q arrives PRE-SCALED, so s needs no per-element scale and
    # ds = p·(dp−δ) carries none either; the missing factor lands once on
    # the [Bq, D] accumulator at finalize (dq = scale·ds@k).
    qi = pl.program_id(1)
    step = pl.program_id(2)
    ki = step
    if window is not None:
        ki = _first_k(qi, block_q, block_k, window) + step

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]                        # [Bq, 1]
        delta = delta_ref[0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k, window)
        p = jnp.exp(s - lse)                            # [Bq, Bk] f32
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        computed = ki * block_k <= qi * block_q + block_q - 1
        full = qi * block_q >= ki * block_k + block_k - 1
        if window is not None:
            computed, full = _in_band(computed, full, qi, ki, block_q,
                                      block_k, window)
        pl.when(computed & full)(lambda: _compute(False))
        pl.when(computed & jnp.logical_not(full))(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(step == num_k - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, causal,
                block_q, block_k, num_q, window=None, group=1,
                q_blocks=None):
    # q arrives PRE-SCALED: s needs no per-element scale, and
    # dk = scale·(dsᵀ@q_unscaled) = dsᵀ@q_scaled — the factor is already
    # in the q operand, so no fixup anywhere.
    # ``num_q`` is the grid's extent: every q block, or the band's steps.
    # With grouped KV heads the grid has one more axis, the group's query
    # heads, between the k block and the q steps: one K/V head's dk and dv
    # stay in the accumulators while all of them pass.
    ki = pl.program_id(1)
    step = pl.program_id(2 if group == 1 else 3)
    qi = step
    if window is not None:
        qi = _first_q(ki, block_q, block_k) + step
    first = step == 0
    if group > 1:
        first = first & (pl.program_id(2) == 0)

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Bq, Bk]
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k, window)
        p = jnp.exp(s - lse)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Bk, D]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                            # [Bq, Bk]
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        computed = qi * block_q + block_q - 1 >= ki * block_k
        full = qi * block_q >= ki * block_k + block_k - 1
        if window is not None:
            # a step past the sequence's last q block is no block at all
            computed, full = _in_band(computed & (qi < q_blocks), full, qi,
                                      ki, block_q, block_k, window)
        pl.when(computed & full)(lambda: _compute(False))
        pl.when(computed & jnp.logical_not(full))(lambda: _compute(True))
    else:
        _compute(False)

    last = step == num_q - 1
    if group > 1:
        last = last & (pl.program_id(2) == group - 1)

    @pl.when(last)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                causal, block_q, block_k, num_q, num_k, window=None,
                group=1, q_blocks=None):
    # The whole backward of one tile: s, the mask, p, dp and ds are built
    # ONCE and feed all three gradients (five matmuls where the dq and dkv
    # kernels issue seven, one pass of exp where they make two).  Grid (K/V
    # head, query head of its group, k block, q step).  A tile adds along a
    # row of tiles (dq) and along a column (dk, dv), so dq is held for the
    # WHOLE sequence of the query head, float32 [Tq, D], and a tile adds
    # into its q block's rows.  dk and dv are the block the q steps stay on;
    # with grouped K/V heads their k blocks come round once a query head, so
    # they too are held whole, [Tk, D] each, while the group passes.
    # ``num_q`` is the grid's extent: every q block, or the band's steps.
    # The tile is built TRANSPOSED, [Bk, Bq]: dv += p^T do and dk += ds^T q
    # are then plain matmuls, only dq contracts a leading axis, and lse and
    # delta arrive as rows [1, Bq] (no lane-broadcast copies in HBM).
    # q arrives PRE-SCALED as in the two kernels above.
    g = pl.program_id(1)
    ki = pl.program_id(2)
    step = pl.program_id(3)
    qi = step
    if window is not None:
        qi = _first_q(ki, block_q, block_k) + step
    first, last = step == 0, step == num_q - 1
    kv_rows = slice(None)
    if group > 1:
        first = first & (g == 0) & (ki == 0)
        last = last & (g == group - 1) & (ki == num_k - 1)
        kv_rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)

    @pl.when((ki == 0) & (step == 0))
    def _init_q():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _init_kv():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        q = q_ref[0]                                     # [Bq, D]
        k = k_ref[0]                                     # [Bk, D]
        v = v_ref[0]
        do = do_ref[0]
        nt = (((1,), (1,)), ((), ()))
        st = jax.lax.dot_general(k, q, nt,
                                 preferred_element_type=jnp.float32)
        if masked:
            st = _causal_mask(st, qi, ki, block_q, block_k, window,
                              q_axis=1)
        pt = jnp.exp(st - lse_ref[0])                    # [Bk, Bq] f32
        dpt = jax.lax.dot_general(v, do, nt,
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0])).astype(q.dtype)
        dv_acc[kv_rows, :] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Bk, D]
        dk_acc[kv_rows, :] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        q_rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[q_rows, :] += jax.lax.dot_general(
            dst, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [Bq, D]

    if causal:
        computed = qi * block_q + block_q - 1 >= ki * block_k
        full = qi * block_q >= ki * block_k + block_k - 1
        if window is not None:
            # a step past the sequence's last q block is no block at all
            computed, full = _in_band(computed & (qi < q_blocks), full, qi,
                                      ki, block_q, block_k, window)
        pl.when(computed & full)(lambda: _compute(False))
        pl.when(computed & jnp.logical_not(full))(lambda: _compute(True))
    else:
        _compute(False)

    @pl.when(last)
    def _finalize_kv():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((ki == num_k - 1) & (step == num_q - 1))
    def _finalize_q():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _named_call(name, kernel, **kwargs):
    """``pl.pallas_call`` named ``name`` under a scope of the same name:
    the compiled program names the custom call after the innermost scope
    (``flash_fwd.3``), so a device trace tells the three kernels apart."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def scoped(*operands):
        with jax.named_scope(name):
            return call(*operands)

    return scoped


def _kv_index(block_q, block_k, window, group, causal, fold=None):
    """Index map of a K/V block for the grids ``(head, q block, k step)``:
    query head ``b`` reads K/V head ``b // group`` (nothing is repeated in
    HBM); with a window, step ``j`` is the band's j-th block.  A causal
    row's steps past its last computed block keep that block's index: they
    are skipped, and nothing is fetched for them.  On the folded grid
    (``fold``) every step is a tile, ``_fold_step``'s."""
    def index(b, i, j):
        if fold is not None:
            j = _fold_step(i, j, *fold)[1]
        else:
            if window is not None:
                j = _first_k(i, block_q, block_k, window) + j
            if causal:
                j = jnp.minimum(j, _last_k(i, block_q, block_k))
        return (b if group == 1 else b // group, j, 0)

    return index


def _fwd_plan(causal, window, num_q, num_k, block_q, block_k):
    """A forward call's grid, from its shapes (``_fwd_grid``): ``(kind, fold,
    (rows, steps), q_index, kv_index)``.  ``fold`` is the kernel's (``(num_q,
    r)``, or ``None`` for a grid ``(head, q block, k step)``), ``q_index`` the
    index map of the q, o and lse blocks and ``kv_index(group)`` that of a
    K/V block read by ``group`` query heads."""
    kind = _fwd_grid(causal, window, num_q, block_q, block_k)
    fold = None
    if kind == "folded":
        fold = (num_q, block_k // block_q)
        num_q, num_k = num_q // 2, num_k + 1
    elif kind == "band":
        num_k, _ = _band_steps(num_q, num_k, block_q, block_k, window)

    def q_index(b, i, j):
        return (b, i if fold is None else _fold_step(i, j, *fold)[0], 0)

    def kv_index(group):
        return _kv_index(block_q, block_k, window, group, causal, fold)

    return kind, fold, (num_q, num_k), q_index, kv_index


def _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret,
              window=None, group=1):
    """q [bh, Tq, D], k/v [bh / group, Tk, D] → (o [bh, Tq, D], lse [bh, Tq]
    f32)."""
    from .. import metrics

    bh, Tq, D = q.shape
    Tk = k.shape[1]
    num_q = Tq // block_q
    num_k = Tk // block_k
    kind, fold, grid, q_index, kv_index = _fwd_plan(
        causal, window, num_q, num_k, block_q, block_k)
    metrics.counter("attention.fwd_traced", {"grid": kind}).inc()
    # Scale folded into q ([T, D] once), not into every [Bq, Bk] score.
    with elem():
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    kernel = functools.partial(_fwd_kernel, causal=causal,
                               block_q=block_q, block_k=block_k,
                               num_k=grid[1], window=window, fold=fold)
    kv_spec = pl.BlockSpec((1, block_k, D), kv_index(group))
    o, lse = _named_call(
        "flash_fwd" if window is None else "flash_win_fwd",
        kernel,
        grid=(bh, *grid),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            kv_spec,
            kv_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_q, _LANES), q_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((bh, Tq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    with elem():
        return o, lse[:, :, 0]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, scale, causal, block_q, block_k, block_q_bwd,
           block_k_bwd, interpret, window, group):
    return _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret,
                     window, group)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, block_q_bwd,
               block_k_bwd, interpret, window, group):
    o, lse = _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret,
                       window, group)
    # Remat seam: under jax.checkpoint the partial-eval inlines this fwd
    # rule, so naming the kernel outputs lets a policy SAVE them — the
    # backward then feeds its kernel directly instead of
    # replaying the forward kernel to regenerate its residuals (a ~12%
    # remat tax: pre-round figure, record removed in PR 21 — a claim to
    # re-measure).  models/transformer.py's
    # "dots" policy saves both names; costs one o-sized buffer per
    # layer (lse is ~D× smaller).  (What remat does to a saved value, its
    # ``reduce_precision``, takes the name stack of these two.)
    with elem():
        o = checkpoint_name(o, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
    return (o, lse), (q, k, v, o, lse)


# What the fused backward may hold in VMEM for as long as a head takes: dq,
# float32 [Tq, D], and the output block it is cast into (double-buffered);
# with grouped K/V heads dk and dv and their blocks as well.  32 MiB admits
# 32,768 tokens at D=128 in bfloat16 (10,922 with groups; the cells hold 2
# MiB at 2048 tokens, 8 at 8192, Laguna's grouped layers 24) and leaves the
# tile's own intermediates (some 20 MiB at 1024 x 1024) inside the limit the
# call asks of a v5e core's 128 MiB.
_FUSED_RESIDENT_BYTES = 32 * 2 ** 20
_FUSED_VMEM_LIMIT = 100 * 2 ** 20


def _fused_fits(Tq, Tk, D, group, dtype, block_q):
    """The ONE rule that places the backward's accumulators, from the
    shapes alone: fused (dq resident for a whole query head; dk and dv too
    for a K/V head with a group) when they fit ``_FUSED_RESIDENT_BYTES`` and
    a q block's row statistics make whole lanes; the dq and dkv kernels
    otherwise."""
    itemsize = jnp.dtype(dtype).itemsize
    resident = Tq * D * (4 + 2 * itemsize)
    if group > 1:
        resident += Tk * D * 2 * (4 + 2 * itemsize)
    return (resident <= _FUSED_RESIDENT_BYTES
            and (block_q % _LANES == 0 or block_q == Tq))


def _flash_bwd(scale, causal, block_q, block_k, block_q_bwd, block_k_bwd,
               interpret, window, group, res, cts):
    # The backward runs its own (larger) blocks: 1024 x 1024 is the fused
    # call's fastest at every cell's shape, 512 x 512 under a 512-key window
    # (swept on a v5e: PERF.md section 6, PR 35).
    from .. import metrics

    block_q, block_k = block_q_bwd, block_k_bwd
    q, k, v, o, lse = res
    do, dlse = cts
    # Δ_i = Σ_d do·o − dlse: the lse cotangent enters exactly where the
    # softmax normalizer does (∂lse/∂s_ij = p_ij), so it folds into delta.
    # Same pre-scaled-q convention as the forward (see kernel docstrings:
    # dq re-applies the factor at finalize; dk absorbs it via the q
    # operand; dv never needs it).
    with elem():
        delta = (jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
                 - dlse.astype(jnp.float32))             # [bh, Tq]
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    fused = _fused_fits(q.shape[1], k.shape[1], q.shape[2], group, q.dtype,
                        block_q)
    metrics.counter("attention.bwd_traced",
                    {"path": "fused" if fused else "split"}).inc()
    return (_bwd_fused if fused else _bwd_split)(
        q, k, v, do, lse, delta, scale, causal, block_q, block_k, interpret,
        window, group)


def _bwd_fused(q, k, v, do, lse, delta, scale, causal, block_q, block_k,
               interpret, window, group):
    """dq, dk, dv from ONE call (``flash_bwd``; with a window
    ``flash_win_bwd``); q pre-scaled, lse and delta [bh, Tq] float32."""
    bh, Tq, D = q.shape
    Tk = k.shape[1]
    num_q = Tq // block_q
    num_k = Tk // block_k
    q_steps = num_q
    if window is not None:
        _, q_steps = _band_steps(num_q, num_k, block_q, block_k, window)

    # grid (K/V head, query head of its group, k block, q step)
    def q_block(i, j):
        # a step outside the column's computed blocks keeps the nearest of
        # them: it is skipped, and nothing is fetched for it
        if window is not None:
            return jnp.minimum(_first_q(i, block_q, block_k) + j,
                               _last_q(i, block_q, block_k, window, num_q))
        return jnp.maximum(j, _first_q(i, block_q, block_k)) if causal else j

    def q_index(b, g, i, j):
        return (b * group + g, q_block(i, j), 0)

    def row_index(b, g, i, j):
        return (b * group + g, 0, q_block(i, j))

    def k_index(b, g, i, j):
        return (b, i, 0)

    q_spec = pl.BlockSpec((1, block_q, D), q_index)
    k_spec = pl.BlockSpec((1, block_k, D), k_index)
    row_spec = pl.BlockSpec((1, 1, block_q), row_index)
    # dk and dv: the k block's own accumulator, or with a group the whole
    # K/V head's
    kv_out, kv_rows = k_spec, block_k
    if group > 1:
        kv_out = pl.BlockSpec((1, Tk, D), lambda b, g, i, j: (b, 0, 0))
        kv_rows = Tk
    with elem():
        lse, delta = lse[:, None, :], delta[:, None, :]
    return _named_call(
        "flash_bwd" if window is None else "flash_win_bwd",
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=q_steps,
                          num_k=num_k, window=window, group=group,
                          q_blocks=num_q),
        grid=(bh // group, group, num_k, q_steps),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((1, Tq, D),
                                lambda b, g, i, j: (b * group + g, 0, 0)),
                   kv_out, kv_out],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((Tq, D), jnp.float32),
                        pltpu.VMEM((kv_rows, D), jnp.float32),
                        pltpu.VMEM((kv_rows, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _bwd_split(q, k, v, do, lse, delta, scale, causal, block_q, block_k,
               interpret, window, group):
    """dq and dk/dv from a kernel each (``flash_bwd_dq``, ``flash_bwd_dkv``;
    ``flash_win_*`` with a window): what runs where the fused call's whole-
    sequence accumulators do not fit beside the tile.  Same operands as
    ``_bwd_fused``."""
    bh, Tq, D = q.shape
    Tk = k.shape[1]
    num_q = Tq // block_q
    num_k = Tk // block_k
    k_steps, q_steps = num_k, num_q
    if window is not None:
        k_steps, q_steps = _band_steps(num_q, num_k, block_q, block_k,
                                       window)
    with elem():
        lse_b = jnp.broadcast_to(lse[:, :, None], (bh, Tq, _LANES))
        delta_b = jnp.broadcast_to(delta[:, :, None], (bh, Tq, _LANES))

    row_spec = pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, D),
                           _kv_index(block_q, block_k, window, group, causal))
    dq = _named_call(
        "flash_bwd_dq" if window is None else "flash_win_bwd_dq",
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k=k_steps,
                          window=window),
        grid=(bh, num_q, k_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            row_spec,
            row_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b)

    # The dkv grid is (K/V head, k block, [query head of the group,] q
    # step): the index maps below take ``*g`` so that one set serves both.
    def q_index(b, i, *g_j):
        j = g_j[-1]
        if window is not None:
            j = jnp.minimum(_first_q(i, block_q, block_k) + j,
                            _last_q(i, block_q, block_k, window, num_q))
        return (b if group == 1 else b * group + g_j[0], j, 0)

    def k_index(b, i, *g_j):
        return (b, i, 0)

    dk, dv = _named_call(
        "flash_bwd_dkv" if window is None else "flash_win_bwd_dkv",
        functools.partial(_dkv_kernel, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=q_steps,
                          window=window, group=group, q_blocks=num_q),
        grid=((bh, num_k, q_steps) if group == 1
              else (bh // group, num_k, group, q_steps)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_k, D), k_index),
            pl.BlockSpec((1, block_k, D), k_index),
            pl.BlockSpec((1, block_q, D), q_index),
            pl.BlockSpec((1, block_q, _LANES), q_index),
            pl.BlockSpec((1, block_q, _LANES), q_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), k_index),
            pl.BlockSpec((1, block_k, D), k_index),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse_b, delta_b)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, scale: Optional[float] = None,
                    causal: bool = True,
                    block_q: int = 512, block_k: int = 1024,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    interpret: bool = False,
                    return_lse: bool = False,
                    window: Optional[int] = None,
                    kv_heads: Optional[int] = None):
    """q [B,H,Tq,D], k/v [B,KV,Tk,D] → [B,H,Tq,D] (and lse [B,H,Tq] f32).

    ``kv_heads`` (``KV``; ``None`` = ``H``) divides ``H``: query head ``j``
    reads K/V head ``j // (H // KV)`` through the kernels' index maps, and
    dk/dv come back ``[B,KV,Tk,D]``, summed over a group's query heads
    inside the backward kernel.  ``window`` (with ``causal``) keeps key
    ``s`` for query ``t`` iff ``t - window < s <= t``; the kernels then walk
    the band's blocks alone and are named ``flash_win_*``.  Blocks as wide
    as the window are the default there (block_k and the backward's blocks
    are capped at 512): a wider block is mostly outside a 512-key band.

    The backward is one kernel (``flash_bwd``: dq, dk and dv from tiles
    built once) wherever one query head's dq (and with a group the K/V
    head's dk and dv) fits in VMEM beside the tile, and the dq and dkv
    kernels otherwise; the shapes decide (``_fused_fits``), no argument
    does.

    The forward's grid follows the shapes as well (``_fwd_grid``): causal
    without a window it is folded, one row of ``num_k + 1`` steps for q
    blocks ``p`` and ``num_q - 1 - p`` and a tile on every step, where
    ``num_q`` is even and ``block_k`` a multiple of ``block_q`` (every
    power-of-two length from two q blocks up); any other causal call walks
    every k block and fetches nothing for the steps it skips; a call that is
    not causal computes every block.

    ``causal=True`` requires Tq == Tk (the standard aligned causal mask);
    cross-length blocks (ring attention's low/high steps) use
    ``causal=False``.  Fully differentiable via ``jax.custom_vjp`` —
    including through the lse output, so ring-step combinations
    backpropagate correctly.

    Block defaults are measured on v5e at D=128.  Forward 512×1024: ~2.6×
    the 128×128 blocks of rounds 1-3 (a pre-round figure).  Backward 1024×
    1024, swept again for the fused kernel in PR 35 (PERF.md section 6): a
    1024-tile takes it 7.7 us (the split pair 12.7), 512-wide blocks 8.1-9.2
    us for the same area; under a 512-key window 512×512 is the fastest
    (narrower blocks lose 16%, wider ones 40%).  VMEM at 1024×1024: four
    float32 intermediates of 4 MiB; the fused call asks for its own limit
    (``_FUSED_VMEM_LIMIT``).  At head dims well beyond 128, pass smaller
    blocks.
    """
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if causal and Tq != Tk:
        raise ValueError(f"causal flash attention needs Tq == Tk, got "
                         f"{Tq} != {Tk}")
    if scale is None:
        scale = D ** -0.5
    KV = k.shape[1]
    if kv_heads is not None and kv_heads != KV:
        raise ValueError(f"kv_heads={kv_heads} but k holds {KV} heads")
    if H % KV or v.shape[1] != KV:
        raise ValueError(f"{H} query heads do not divide into k's {KV} and "
                         f"v's {v.shape[1]} K/V heads")
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window needs causal=True and window >= 1, "
                             f"got causal={causal}, window={window}")
        block_k = min(block_k, _WINDOW_BLOCK_CAP)
        block_q_bwd = min(block_q_bwd or _WINDOW_BLOCK_CAP,
                          _WINDOW_BLOCK_CAP)
        block_k_bwd = min(block_k_bwd or _WINDOW_BLOCK_CAP,
                          _WINDOW_BLOCK_CAP)

    block_q = fit_block(block_q, Tq)
    block_k = fit_block(block_k, Tk)
    if block_q < 8 or block_k < 8:
        raise ValueError(f"no usable block size (>=8) divides "
                         f"Tq={Tq}, Tk={Tk}")
    # Backward blocks default to 1024x1024 (the fused kernel's sweep, PR
    # 35), VMEM-scaled for large head dims like the forward caps.  When the
    # pow2 default cannot divide an odd T, fall back to the (validated)
    # forward blocks rather than failing a call that may never be
    # differentiated; only EXPLICIT bad bwd blocks raise.
    explicit_bwd = block_q_bwd is not None or block_k_bwd is not None
    if block_q_bwd is None:
        block_q_bwd = scale_cap_for_head_dim(1024, D)
    if block_k_bwd is None:
        block_k_bwd = scale_cap_for_head_dim(1024, D)
    block_q_bwd = fit_block(block_q_bwd, Tq)
    block_k_bwd = fit_block(block_k_bwd, Tk)
    if block_q_bwd < 8 or block_k_bwd < 8:
        if explicit_bwd:
            raise ValueError(f"no usable bwd block size (>=8) divides "
                             f"Tq={Tq}, Tk={Tk}")
        block_q_bwd, block_k_bwd = block_q, block_k
    with elem():
        q = q.reshape(B * H, Tq, D)
        k, v = k.reshape(B * KV, Tk, D), v.reshape(B * KV, Tk, D)
    o, lse = _flash(q, k, v, float(scale), bool(causal),
                    int(block_q), int(block_k), int(block_q_bwd),
                    int(block_k_bwd), bool(interpret),
                    None if window is None else int(window), H // KV)
    with elem():
        o = o.reshape(B, H, Tq, D)
        if return_lse:
            return o, lse.reshape(B, H, Tq)
        return o


# ---------------------------------------------------------------- two widths
# Latent attention's decompressed form: scores from an unrotated part a head
# and a rotated part whose key is one head shared by every query head;
# values of their own width.  Causal, no window.  Same streaming softmax,
# same pre-scaled q (both parts), same grid and block classes as above.
def _mla_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, lse_ref,
                    acc, m_scr, l_scr, *, block_q, block_k, num_k,
                    fold=None):
    # ``_fwd_kernel``'s grid: folded (``fold``), or every k block, clamped
    qi, ki, first, last = _fwd_step(fold, num_k)

    @pl.when(first)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    def _compute(masked):
        v = v_ref[0]
        s = _mla_scores(qn_ref[0], qr_ref[0], kn_ref[0], kr_ref[0])
        if masked:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_new

    _causal_tile(_compute, qi, ki, block_q, block_k,
                 every_step_computed=fold is not None)

    @pl.when(last)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0:1], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _mla_scores(qn, qr, kn, kr):
    """``q_n k_n^T + q_r k_r^T`` of one tile, float32 [Bq, Bk]."""
    dims = (((1,), (1,)), ((), ()))
    return (jax.lax.dot_general(qn, kn, dims,
                                preferred_element_type=jnp.float32)
            + jax.lax.dot_general(qr, kr, dims,
                                  preferred_element_type=jnp.float32))


def _mla_p_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
              delta_ref, qi, ki, block_q, block_k, masked):
    """The probabilities and ``ds = p (dp - delta)`` of one tile, rebuilt
    from the saved row statistics; both float32 [Bq, Bk]."""
    s = _mla_scores(qn_ref[0], qr_ref[0], kn_ref[0], kr_ref[0])
    if masked:
        s = _causal_mask(s, qi, ki, block_q, block_k)
    p = jnp.exp(s - lse_ref[0][:, 0:1])
    dp = jax.lax.dot_general(do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta_ref[0][:, 0:1])


def _mla_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dqn_ref, dqr_ref, dqn_acc, dqr_acc, *, scale,
                   block_q, block_k, num_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dqn_acc[:] = jnp.zeros_like(dqn_acc)
        dqr_acc[:] = jnp.zeros_like(dqr_acc)

    def _compute(masked):
        _, ds = _mla_p_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                          lse_ref, delta_ref, qi, ki, block_q, block_k,
                          masked)
        kn, kr = kn_ref[0], kr_ref[0]
        ds = ds.astype(kn.dtype)
        dims = (((1,), (0,)), ((), ()))
        dqn_acc[:] += jax.lax.dot_general(
            ds, kn, dims, preferred_element_type=jnp.float32)
        dqr_acc[:] += jax.lax.dot_general(
            ds, kr, dims, preferred_element_type=jnp.float32)

    computed = ki * block_k <= qi * block_q + block_q - 1
    full = qi * block_q >= ki * block_k + block_k - 1
    pl.when(computed & full)(lambda: _compute(False))
    pl.when(computed & jnp.logical_not(full))(lambda: _compute(True))

    @pl.when(ki == num_k - 1)
    def _finalize():
        dqn_ref[0] = (dqn_acc[:] * scale).astype(dqn_ref.dtype)
        dqr_ref[0] = (dqr_acc[:] * scale).astype(dqr_ref.dtype)


def _mla_dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dkn_ref, dkr_ref, dv_ref, dkn_acc, dkr_acc,
                    dv_acc, *, block_q, block_k, num_q, heads):
    # Grid (batch, k block, query head, q block): a head's dk_n and dv are
    # written when its q blocks are through; the shared rotated part's dk_r
    # stays in its accumulator while all the heads pass.
    ki = pl.program_id(1)
    head = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dkn_acc[:] = jnp.zeros_like(dkn_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when((qi == 0) & (head == 0))
    def _init_shared():
        dkr_acc[:] = jnp.zeros_like(dkr_acc)

    def _compute(masked):
        p, ds = _mla_p_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref,
                          lse_ref, delta_ref, qi, ki, block_q, block_k,
                          masked)
        qn, qr, do = qn_ref[0], qr_ref[0], do_ref[0]
        dims = (((0,), (0,)), ((), ()))
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, dims, preferred_element_type=jnp.float32)
        ds = ds.astype(qn.dtype)
        dkn_acc[:] += jax.lax.dot_general(
            ds, qn, dims, preferred_element_type=jnp.float32)
        dkr_acc[:] += jax.lax.dot_general(
            ds, qr, dims, preferred_element_type=jnp.float32)

    computed = qi * block_q + block_q - 1 >= ki * block_k
    full = qi * block_q >= ki * block_k + block_k - 1
    pl.when(computed & full)(lambda: _compute(False))
    pl.when(computed & jnp.logical_not(full))(lambda: _compute(True))

    @pl.when(qi == num_q - 1)
    def _finalize():
        dkn_ref[0] = dkn_acc[:].astype(dkn_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((qi == num_q - 1) & (head == heads - 1))
    def _finalize_shared():
        dkr_ref[0] = dkr_acc[:].astype(dkr_ref.dtype)


def _mla_fwd_impl(qn, qr, kn, kr, v, scale, heads, block_q, block_k,
                  interpret):
    """qn/kn [bh, T, Dn], qr [bh, T, Dr], kr [b, T, Dr], v [bh, T, Dv] →
    (o [bh, T, Dv], lse [bh, T] f32)."""
    from .. import metrics

    bh, T, Dn = qn.shape
    Dr, Dv = qr.shape[-1], v.shape[-1]
    num_q, num_k = T // block_q, T // block_k
    # ``_fwd_impl``'s grid; the rotated key is one head that all ``heads``
    # read
    kind, fold, grid, q_index, kv_index = _fwd_plan(
        True, None, num_q, num_k, block_q, block_k)
    metrics.counter("attention.fwd_traced", {"grid": kind}).inc()
    with elem():
        qn = (qn.astype(jnp.float32) * scale).astype(qn.dtype)
        qr = (qr.astype(jnp.float32) * scale).astype(qr.dtype)

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width), q_index)

    def k_spec(width):
        return pl.BlockSpec((1, block_k, width), kv_index(1))

    o, lse = _named_call(
        "flash_mla_fwd",
        functools.partial(_mla_fwd_kernel, block_q=block_q, block_k=block_k,
                          num_k=grid[1], fold=fold),
        grid=(bh, *grid),
        in_specs=[q_spec(Dn), q_spec(Dr), k_spec(Dn),
                  pl.BlockSpec((1, block_k, Dr), kv_index(heads)),
                  k_spec(Dv)],
        out_specs=[q_spec(Dv), q_spec(_LANES)],
        out_shape=[jax.ShapeDtypeStruct((bh, T, Dv), v.dtype),
                   jax.ShapeDtypeStruct((bh, T, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, Dv), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=interpret,
    )(qn, qr, kn, kr, v)
    with elem():
        return o, lse[:, :, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_mla(qn, qr, kn, kr, v, scale, heads, block_q, block_k,
               block_q_bwd, block_k_bwd, interpret):
    return _mla_fwd_impl(qn, qr, kn, kr, v, scale, heads, block_q, block_k,
                         interpret)


def _flash_mla_fwd(qn, qr, kn, kr, v, scale, heads, block_q, block_k,
                   block_q_bwd, block_k_bwd, interpret):
    o, lse = _mla_fwd_impl(qn, qr, kn, kr, v, scale, heads, block_q, block_k,
                           interpret)
    # the same remat seam as ``_flash_fwd``'s
    with elem():
        o = checkpoint_name(o, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
    return (o, lse), (qn, qr, kn, kr, v, o, lse)


def _mla_bwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dqn_ref, dqr_ref, dkn_ref, dkr_ref, dv_ref,
                    dqn_acc, dqr_acc, dkn_acc, dkr_acc, dv_acc, *, scale,
                    block_q, block_k, num_q, num_k, heads):
    # ``_bwd_kernel`` at two widths: the two-part scores, the mask, p, dp and
    # ds of a tile are built ONCE and feed all five gradients (eight MXU
    # passes a tile where the dq and dkv kernels issue eleven, one pass of
    # exp for two).  Grid (batch, query head, k block, q step).  dq_n and
    # dq_r are held for the WHOLE sequence of the query head, float32, a
    # tile adding into its q block's rows; dk_n and dv are the block the q
    # steps stay on; dk_r, one head shared by all, is held whole while every
    # head of the batch element passes (``_bwd_kernel``'s group, the group
    # being all the heads).  The tile is built TRANSPOSED, [Bk, Bq]: dv, dk_n
    # and dk_r are plain matmuls, only the two dq contract a leading axis,
    # and lse and delta arrive as rows [1, Bq].  q_n, q_r PRE-SCALED.
    head = pl.program_id(1)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    first_of_head = (ki == 0) & (qi == 0)
    last_of_head = (ki == num_k - 1) & (qi == num_q - 1)

    @pl.when(first_of_head)
    def _init_q():
        dqn_acc[:] = jnp.zeros_like(dqn_acc)
        dqr_acc[:] = jnp.zeros_like(dqr_acc)

    @pl.when(first_of_head & (head == 0))
    def _init_shared():
        dkr_acc[:] = jnp.zeros_like(dkr_acc)

    @pl.when(qi == 0)
    def _init_kv():
        dkn_acc[:] = jnp.zeros_like(dkn_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        qn, qr, do = qn_ref[0], qr_ref[0], do_ref[0]     # [Bq, *]
        kn, kr = kn_ref[0], kr_ref[0]                    # [Bk, *]
        st = _mla_scores(kn, kr, qn, qr)                 # [Bk, Bq]
        if masked:
            st = _causal_mask(st, qi, ki, block_q, block_k, q_axis=1)
        pt = jnp.exp(st - lse_ref[0])
        dpt = jax.lax.dot_general(v_ref[0], do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta_ref[0])).astype(qn.dtype)
        plain = (((1,), (0,)), ((), ()))
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, plain,
            preferred_element_type=jnp.float32)          # [Bk, Dv]
        dkn_acc[:] += jax.lax.dot_general(
            dst, qn, plain, preferred_element_type=jnp.float32)
        k_rows = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        dkr_acc[k_rows, :] += jax.lax.dot_general(
            dst, qr, plain, preferred_element_type=jnp.float32)
        q_rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        leading = (((0,), (0,)), ((), ()))
        dqn_acc[q_rows, :] += jax.lax.dot_general(
            dst, kn, leading, preferred_element_type=jnp.float32)
        dqr_acc[q_rows, :] += jax.lax.dot_general(
            dst, kr, leading, preferred_element_type=jnp.float32)

    computed = qi * block_q + block_q - 1 >= ki * block_k
    full = qi * block_q >= ki * block_k + block_k - 1
    pl.when(computed & full)(lambda: _compute(False))
    pl.when(computed & jnp.logical_not(full))(lambda: _compute(True))

    @pl.when(qi == num_q - 1)
    def _finalize_kv():
        dkn_ref[0] = dkn_acc[:].astype(dkn_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(last_of_head)
    def _finalize_q():
        dqn_ref[0] = (dqn_acc[:] * scale).astype(dqn_ref.dtype)
        dqr_ref[0] = (dqr_acc[:] * scale).astype(dqr_ref.dtype)

    @pl.when(last_of_head & (head == heads - 1))
    def _finalize_shared():
        dkr_ref[0] = dkr_acc[:].astype(dkr_ref.dtype)


def _mla_fused_fits(T, Dn, Dr, dtype, block_q):
    """``_fused_fits`` for the two-width backward, from the shapes alone: one
    call (``flash_mla_bwd``) when what it holds while a head (dq_n, dq_r) or
    a batch element (dk_r) passes, the float32 accumulators and the
    double-buffered output blocks they are cast into, fits
    ``_FUSED_RESIDENT_BYTES`` (16 MiB at Xing's 8,192 tokens in bfloat16, 32
    at Ling's 16,384, the longest that does; the tile's own intermediates,
    some 36 MiB at 1024 x 1024 with the two dq results, stand beside them
    inside ``_FUSED_VMEM_LIMIT``) and a q block's row statistics make whole
    lanes; the dq and dkv kernels otherwise."""
    resident = T * (Dn + 2 * Dr) * (4 + 2 * jnp.dtype(dtype).itemsize)
    return (resident <= _FUSED_RESIDENT_BYTES
            and (block_q % _LANES == 0 or block_q == T))


def _flash_mla_bwd(scale, heads, block_q, block_k, block_q_bwd, block_k_bwd,
                   interpret, res, cts):
    from .. import metrics

    block_q, block_k = block_q_bwd, block_k_bwd
    qn, qr, kn, kr, v, o, lse = res
    do, dlse = cts
    with elem():
        delta = (jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
                 - dlse.astype(jnp.float32))
        qn = (qn.astype(jnp.float32) * scale).astype(qn.dtype)
        qr = (qr.astype(jnp.float32) * scale).astype(qr.dtype)
    fused = _mla_fused_fits(qn.shape[1], qn.shape[2], qr.shape[2], qn.dtype,
                            block_q)
    metrics.counter("attention.latent_bwd_traced",
                    {"path": "fused" if fused else "split"}).inc()
    return (_mla_bwd_fused if fused else _mla_bwd_split)(
        qn, qr, kn, kr, v, do, lse, delta, scale, heads, block_q, block_k,
        interpret)


def _mla_bwd_fused(qn, qr, kn, kr, v, do, lse, delta, scale, heads, block_q,
                   block_k, interpret):
    """dq_n, dq_r, dk_n, dk_r, dv from ONE call (``flash_mla_bwd``); q_n and
    q_r pre-scaled, lse and delta [bh, T] float32."""
    bh, T, Dn = qn.shape
    Dr, Dv = qr.shape[-1], v.shape[-1]
    num_q, num_k = T // block_q, T // block_k

    # grid (batch, query head, k block, q step); a step above the column's
    # computed blocks keeps the first of them: it is skipped, and nothing is
    # fetched for it
    def q_block(i, j):
        return jnp.maximum(j, _first_q(i, block_q, block_k))

    def of_q(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, h, i, j: (b * heads + h, q_block(i, j),
                                                0))

    def of_k(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, h, i, j: (b * heads + h, i, 0))

    def of_head(width):
        return pl.BlockSpec((1, T, width),
                            lambda b, h, i, j: (b * heads + h, 0, 0))

    row_spec = pl.BlockSpec(
        (1, 1, block_q), lambda b, h, i, j: (b * heads + h, 0, q_block(i, j)))
    with elem():
        lse, delta = lse[:, None, :], delta[:, None, :]
    return _named_call(
        "flash_mla_bwd",
        functools.partial(_mla_bwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, num_q=num_q, num_k=num_k,
                          heads=heads),
        grid=(bh // heads, heads, num_k, num_q),
        in_specs=[of_q(Dn), of_q(Dr), of_k(Dn),
                  pl.BlockSpec((1, block_k, Dr),
                               lambda b, h, i, j: (b, i, 0)),
                  of_k(Dv), of_q(Dv), row_spec, row_spec],
        out_specs=[of_head(Dn), of_head(Dr), of_k(Dn),
                   pl.BlockSpec((1, T, Dr), lambda b, h, i, j: (b, 0, 0)),
                   of_k(Dv)],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype),
                   jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((T, Dn), jnp.float32),
                        pltpu.VMEM((T, Dr), jnp.float32),
                        pltpu.VMEM((block_k, Dn), jnp.float32),
                        pltpu.VMEM((T, Dr), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(qn, qr, kn, kr, v, do, lse, delta)


def _mla_bwd_split(qn, qr, kn, kr, v, do, lse, delta, scale, heads, block_q,
                   block_k, interpret):
    """dq_n, dq_r and dk_n, dk_r, dv from a kernel each (``flash_mla_bwd_dq``,
    ``flash_mla_bwd_dkv``): what runs where the fused call's whole-sequence
    accumulators do not fit beside the tile.  Same operands as
    ``_mla_bwd_fused``."""
    bh, T, Dn = qn.shape
    Dr, Dv = qr.shape[-1], v.shape[-1]
    num_q, num_k = T // block_q, T // block_k
    with elem():
        lse_b = jnp.broadcast_to(lse[:, :, None], (bh, T, _LANES))
        delta_b = jnp.broadcast_to(delta[:, :, None], (bh, T, _LANES))
    operands = (qn, qr, kn, kr, v, do, lse_b, delta_b)

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0))

    def k_spec(width):
        return pl.BlockSpec((1, block_k, width), lambda b, i, j: (b, j, 0))

    dqn, dqr = _named_call(
        "flash_mla_bwd_dq",
        functools.partial(_mla_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, num_k=num_k),
        grid=(bh, num_q, num_k),
        in_specs=[q_spec(Dn), q_spec(Dr), k_spec(Dn),
                  pl.BlockSpec((1, block_k, Dr),
                               lambda b, i, j: (b // heads, j, 0)),
                  k_spec(Dv), q_spec(Dv), q_spec(_LANES), q_spec(_LANES)],
        out_specs=[q_spec(Dn), q_spec(Dr)],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, Dn), jnp.float32),
                        pltpu.VMEM((block_q, Dr), jnp.float32)],
        interpret=interpret,
    )(*operands)

    # grid (batch, k block, query head, q block)
    def of_q(width):
        return pl.BlockSpec((1, block_q, width),
                            lambda b, i, h, j: (b * heads + h, j, 0))

    def of_k(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, i, h, j: (b * heads + h, i, 0))

    shared = pl.BlockSpec((1, block_k, Dr), lambda b, i, h, j: (b, i, 0))
    dkn, dkr, dv = _named_call(
        "flash_mla_bwd_dkv",
        functools.partial(_mla_dkv_kernel, block_q=block_q, block_k=block_k,
                          num_q=num_q, heads=heads),
        grid=(bh // heads, num_k, heads, num_q),
        in_specs=[of_q(Dn), of_q(Dr), of_k(Dn), shared, of_k(Dv), of_q(Dv),
                  of_q(_LANES), of_q(_LANES)],
        out_specs=[of_k(Dn), shared, of_k(Dv)],
        out_shape=[jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(kr.shape, kr.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, Dn), jnp.float32),
                        pltpu.VMEM((block_k, Dr), jnp.float32),
                        pltpu.VMEM((block_k, Dv), jnp.float32)],
        interpret=interpret,
    )(*operands)
    return dqn, dqr, dkn, dkr, dv


_flash_mla.defvjp(_flash_mla_fwd, _flash_mla_bwd)


def flash_attention_latent(q_nope, q_rope, k_nope, k_rope, v,
                           scale: Optional[float] = None,
                           block_q: int = 512, block_k: int = 1024,
                           block_q_bwd: int = 1024, block_k_bwd: int = 1024,
                           interpret: bool = False):
    """Causal attention whose scores are ``q_nope . k_nope + q_rope .
    k_rope``: q_nope/k_nope [B,H,T,Dn], q_rope [B,H,T,Dr], k_rope [B,1,T,Dr]
    (one head for all H), v [B,H,T,Dv] → [B,H,T,Dv].  ``scale`` defaults to
    ``(Dn + Dr) ** -0.5``.  Differentiable in all five (``jax.custom_vjp``);
    ``k_rope``'s gradient comes back ``[B,1,T,Dr]``, summed over the heads
    inside the backward kernel (ONE call, ``flash_mla_bwd``, where the
    shapes fit: ``_mla_fused_fits``).  Blocks shrink to divide ``T`` as
    ``flash_attention``'s do; 1024 x 1024 is the backward's fastest at 8,192
    and 16,384 tokens (swept on a v5e: PERF.md section 6, PR 41)."""
    from .. import metrics

    B, H, T, Dn = q_nope.shape
    Dr, Dv = q_rope.shape[-1], v.shape[-1]
    if (k_nope.shape != (B, H, T, Dn) or q_rope.shape != (B, H, T, Dr)
            or k_rope.shape != (B, 1, T, Dr) or v.shape[:3] != (B, H, T)):
        raise ValueError(
            "flash_attention_latent wants q_nope/k_nope [B,H,T,Dn], q_rope "
            f"[B,H,T,Dr], k_rope [B,1,T,Dr], v [B,H,T,Dv]; got "
            f"{q_nope.shape}, {k_nope.shape}, {q_rope.shape}, "
            f"{k_rope.shape}, {v.shape}")
    if scale is None:
        scale = (Dn + Dr) ** -0.5
    blocks = [fit_block(b, T) for b in (block_q, block_k, block_q_bwd,
                                        block_k_bwd)]
    if min(blocks) < 8:
        raise ValueError(f"no usable block size (>=8) divides T={T}")
    metrics.counter("attention.latent_traced",
                    {"qk": str(Dn + Dr), "v": str(Dv)}).inc()
    with elem():
        flat = (q_nope.reshape(B * H, T, Dn), q_rope.reshape(B * H, T, Dr),
                k_nope.reshape(B * H, T, Dn), k_rope.reshape(B, T, Dr),
                v.reshape(B * H, T, Dv))
    o, _ = _flash_mla(*flat, float(scale), int(H),
                      *(int(b) for b in blocks), bool(interpret))
    with elem():
        return o.reshape(B, H, T, Dv)
