"""EVA attention (arXiv:2302.04542, as EvaByte runs it) as differentiable
Pallas TPU kernels: ONE softmax over two sources of keys.

A query ``t`` of window ``w = t // window`` sees

- its own window's keys ``window * w <= j <= t`` exactly (block-diagonal
  causal: the window does not slide), and
- every EARLIER window through its chunk summaries: chunk ``m`` holds the
  ``chunk`` positions ``[chunk * m, chunk * (m + 1))`` and is one key ``kbar_m``
  and one value ``vbar_m`` (``summarise``); window ``w`` sees the summaries
  ``m < (window // chunk) * w``, a staircase whose steps are whole tiles of
  ``window // chunk`` summaries (128 at EvaByte's 2,048 / 16), none partial,

and the two sets of scores share one normaliser::

    o_t = (sum_S e^{q_t.k_j} v_j + sum_R e^{q_t.kbar_m} vbar_m)
          / (sum_S e^{q_t.k_j} + sum_R e^{q_t.kbar_m})

**The summariser** (``summarise``; plain ``jax.numpy``, small and
memory-bound, its backward by autodiff): softmax pooling over a chunk's keys
with a learned query ``phi`` a head, ``a_j = softmax_{j in m}(scale k_j .
phi)``, ``kbar_m = sum_j a_j k_j + mu``, ``vbar_m = sum_j a_j v_j``; logits,
softmax and the two weighted sums in float32.

**Forward** (``flash_eva_fwd``): grid ``(batch x head, q block, k block of
the window)``, the streaming softmax of ``ops/flash_attention.py`` (q
pre-scaled, float32 scores and statistics, blocks above the diagonal skipped,
only diagonal blocks masked).  A head's summaries are one resident block
(``[T // chunk, D]``: 256 KiB at 16,384 positions); after the window's last k
block the same accumulators take the ``w`` visible tiles in groups of ``g``,
as wide as an own-window tile (``_walk_group``: ``g`` tiles are 512 keys at
EvaByte's 128 a tile; from the shapes alone, and no more than the last window
sees): a loop of dynamic length over the ``w // g`` full groups, then the ``w
% g`` tiles left as ONE block of exactly that many keys (a static body a
remainder), so the streaming softmax's fixed cost is paid once for 512 keys,
nothing is masked and no key is multiplied that the staircase hides.  The
output is normalised once, and the row statistics leave as a row, ``[bh, 1,
T]`` float32 (lane 0 of the scratch, transposed once a q block), as the
backward reads them.

**Backward** (``flash_eva_bwd``): one kernel, grid ``(batch x head, window, k
block, q block)``.  With the forward's row statistics the gradient splits by
source, so the window's tiles are the fused causal backward of
``flash_attention._bwd_kernel`` (``s``, ``p``, ``dp``, ``ds`` built once,
transposed; five matmuls a tile; ``dq`` held for the window in float32 VMEM),
and on a window's first k block, which meets every q block, each q block also
walks the ``w`` summary tiles it saw: ``dkbar`` and ``dvbar`` gather from every
LATER window into float32 accumulators held for the whole head, and the
tiles' ``dq`` adds into the window's.

Off the TPU the same arithmetic is a dense masked softmax over ``[own window
| summaries]`` a window (``jnp`` path; its backward by ``jax.vjp``), or the
kernels in interpret mode under ``MVTPU_FORCE_FLASH``
(``ops/kernel_path.py``).  A trace is counted in
``attention.eva_traced{window=,chunk=,path=mosaic|interpret|jnp}``, the
backward's in ``attention.eva_bwd_traced`` under the same labels, and a trace
through the kernels in ``attention.eva_summary_walk_traced{width=,bodies=}``:
the keys a step of the forward's summary walk takes, and ``g``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_FUSED_VMEM_LIMIT, _LANES, _NEG, _causal_mask,
                              _named_call, fit_block)
from .kernel_path import elem, kernel_path

__all__ = ["eva_attention", "summarise"]

_NT = (((1,), (1,)), ((), ()))          # x @ y^T
_NN = (((1,), (0,)), ((), ()))          # x @ y
_TN = (((0,), (0,)), ((), ()))          # x^T @ y


def summarise(k, v, phi, mu, scale: float, chunk: int):
    """Chunk summaries of ``k``, ``v`` ``[B, H, T, D]`` with ``phi``, ``mu``
    ``[H, D]``: ``(kbar, vbar)`` ``[B, H, T // chunk, D]`` in the inputs'
    dtype (module docstring); the pooling's logits, softmax and sums in
    float32."""
    B, H, T, D = k.shape
    f32 = jnp.float32
    with jax.named_scope("attn.eva.summarise"):
        kc = k.reshape(B, H, T // chunk, chunk, D).astype(f32)
        vc = v.reshape(B, H, T // chunk, chunk, D).astype(f32)
        logits = scale * jnp.sum(kc * phi.astype(f32)[None, :, None, None],
                                 axis=-1)
        a = jax.nn.softmax(logits, axis=-1)[..., None]       # [B,H,M,c,1]
        kbar = jnp.sum(a * kc, axis=3) + mu.astype(f32)[None, :, None]
        vbar = jnp.sum(a * vc, axis=3)
    with elem():
        return kbar.astype(k.dtype), vbar.astype(v.dtype)


# ------------------------------------------------------------------ forward
_WALK_KEYS = 512        # the summary walk's width, an own-window tile's


def _walk_group(per_window, num_w):
    """Staircase tiles a step of the forward's summary walk: as many as make
    ``_WALK_KEYS`` keys, and no more than the last window sees."""
    return max(1, min(_WALK_KEYS // per_window, num_w - 1))


def _fwd_kernel(q_ref, k_ref, v_ref, kbar_ref, vbar_ref, o_ref, lse_ref, acc,
                m_scr, l_scr, *, block_q, block_k, num_k, q_per_window,
                per_window, group):
    # q arrives PRE-SCALED.  ``num_k`` k blocks a window, ``q_per_window`` q
    # blocks; ``per_window`` summaries a window (one tile of the staircase),
    # ``group`` tiles a step of the summary walk.
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    w = qi // q_per_window
    ql = qi % q_per_window                  # the q block inside its window

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)

    def _online(s, v):
        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_scr[:, 0:1] = m_new
        l_scr[:, 0:1] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)

    def _own(masked):
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=jnp.float32)
        if masked:
            s = _causal_mask(s, ql, ki, block_q, block_k)
        _online(s, v_ref[0])

    computed = ki * block_k <= ql * block_q + block_q - 1
    full = ql * block_q >= ki * block_k + block_k - 1
    pl.when(computed & full)(lambda: _own(False))
    pl.when(computed & jnp.logical_not(full))(lambda: _own(True))

    @pl.when(ki == num_k - 1)
    def _summaries_and_finalize():
        def tiles(first, n):
            # ``n`` (static) whole tiles from tile ``first``: one score block
            rows = pl.ds(pl.multiple_of(first * per_window, per_window),
                         n * per_window)
            s = jax.lax.dot_general(q_ref[0], kbar_ref[0, rows, :], _NT,
                                    preferred_element_type=jnp.float32)
            _online(s, vbar_ref[0, rows, :])

        def full_group(u, carry):
            tiles(u * group, group)
            return carry

        groups = w // group
        jax.lax.fori_loop(0, groups, full_group, 0)
        # the remainder exactly: no key multiplied that the staircase hides,
        # no mask, no row past the resident block's end
        for r in range(1, group):
            pl.when(w % group == r)(
                functools.partial(tiles, groups * group, r))
        l = jnp.maximum(l_scr[:, 0:1], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        # the statistics leave as a row, as ``_bwd_kernel`` reads them: lane 0
        # of the scratch, transposed once a q block
        lse = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))
        lse_ref[0] = jnp.transpose(lse)[0:1, :]


def _geometry(T, window, chunk, block_q, block_k):
    """``(window, summaries a window, block_q, block_k)`` for a sequence of
    ``T``: a sequence no longer than the window is one window."""
    window = min(window, T)
    if T % window or window % chunk:
        raise ValueError(
            f"eva_attention: {T} positions do not divide into windows of "
            f"{window}, or the window into chunks of {chunk}")
    block_q, block_k = fit_block(block_q, window), fit_block(block_k, window)
    if block_q < 8 or block_k < 8:
        raise ValueError(f"no usable block size (>=8) divides the window "
                         f"{window}")
    return window, window // chunk, block_q, block_k


def _fwd_call(q, k, v, kbar, vbar, scale, window, per_window, block_q,
              block_k, interpret):
    """q/k/v [bh, T, D], kbar/vbar [bh, M, D] → (o [bh, T, D], lse [bh, T]
    f32)."""
    bh, T, D = q.shape
    M = kbar.shape[1]
    q_per_window, num_k = window // block_q, window // block_k
    with elem():
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def k_index(b, i, j):
        # a block above the diagonal keeps the diagonal's: nothing is fetched
        ql = i % q_per_window
        last = (ql * block_q + block_q - 1) // block_k
        return (b, (i // q_per_window) * num_k + jnp.minimum(j, last), 0)

    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, D), k_index)
    bar_spec = pl.BlockSpec((1, M, D), lambda b, i, j: (b, 0, 0))
    o, lse = _named_call(
        "flash_eva_fwd",
        functools.partial(_fwd_kernel, block_q=block_q, block_k=block_k,
                          num_k=num_k, q_per_window=q_per_window,
                          per_window=per_window,
                          group=_walk_group(per_window, T // window)),
        grid=(bh, T // block_q, num_k),
        in_specs=[q_spec, k_spec, k_spec, bar_spec, bar_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, T, D), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=interpret,
    )(q, k, v, kbar, vbar)
    with elem():
        return o, lse[:, 0, :]


# ----------------------------------------------------------------- backward
def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kbar_ref,
                vbar_ref, dq_ref, dk_ref, dv_ref, dkbar_ref, dvbar_ref,
                dq_acc, dk_acc, dv_acc, dkbar_acc, dvbar_acc, *, scale,
                block_q, block_k, num_w, num_k, num_q, per_window):
    # Grid (head, window, k block of the window, q block of the window).  The
    # tiles are built TRANSPOSED, [keys, Bq], as flash_attention._bwd_kernel
    # builds them: dv and dk are plain matmuls, lse and delta arrive as rows
    # [1, Bq].  q arrives PRE-SCALED: dk and dkbar absorb the factor through
    # the q operand, dq takes it once at its finalize.
    w = pl.program_id(1)
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when((w == 0) & (ki == 0) & (qi == 0))
    def _init_head():
        dkbar_acc[:] = jnp.zeros_like(dkbar_acc)
        dvbar_acc[:] = jnp.zeros_like(dvbar_acc)

    @pl.when((ki == 0) & (qi == 0))
    def _init_window():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_block():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    def _tile(keys, values, masked):
        """``(p^T, ds^T)`` of the tile of ``keys`` against this q block,
        float32 and the operands' dtype, [keys, Bq]."""
        q, do = q_ref[0], do_ref[0]
        st = jax.lax.dot_general(keys, q, _NT,
                                 preferred_element_type=jnp.float32)
        if masked:
            st = _causal_mask(st, qi, ki, block_q, block_k, q_axis=1)
        pt = jnp.exp(st - lse_ref[0])                    # [keys, Bq] f32
        dpt = jax.lax.dot_general(values, do, _NT,
                                  preferred_element_type=jnp.float32)
        return pt, (pt * (dpt - delta_ref[0])).astype(q.dtype)

    def _own(masked):
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        pt, dst = _tile(k, v_ref[0], masked)
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN, preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            dst, q, _NN, preferred_element_type=jnp.float32)
        dq_acc[q_rows, :] += jax.lax.dot_general(
            dst, k, _TN, preferred_element_type=jnp.float32)

    computed = qi * block_q + block_q - 1 >= ki * block_k
    full = qi * block_q >= ki * block_k + block_k - 1
    pl.when(computed & full)(lambda: _own(False))
    pl.when(computed & jnp.logical_not(full))(lambda: _own(True))

    @pl.when(ki == 0)              # the k block that meets every q block
    def _summaries():
        def tile(u, carry):
            rows = pl.ds(pl.multiple_of(u * per_window, per_window),
                         per_window)
            q, do, kb = q_ref[0], do_ref[0], kbar_ref[0, rows, :]
            pt, dst = _tile(kb, vbar_ref[0, rows, :], False)
            dvbar_acc[rows, :] += jax.lax.dot_general(
                pt.astype(do.dtype), do, _NN,
                preferred_element_type=jnp.float32)
            dkbar_acc[rows, :] += jax.lax.dot_general(
                dst, q, _NN, preferred_element_type=jnp.float32)
            dq_acc[q_rows, :] += jax.lax.dot_general(
                dst, kb, _TN, preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, w, tile, 0)

    @pl.when(qi == num_q - 1)
    def _finalize_block():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when((ki == num_k - 1) & (qi == num_q - 1))
    def _finalize_window():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)

    @pl.when((w == num_w - 1) & (ki == num_k - 1) & (qi == num_q - 1))
    def _finalize_head():
        dkbar_ref[0] = dkbar_acc[:].astype(dkbar_ref.dtype)
        dvbar_ref[0] = dvbar_acc[:].astype(dvbar_ref.dtype)


def _bwd_call(q, k, v, kbar, vbar, do, lse, delta, scale, window, per_window,
              block_q, block_k, interpret):
    """``(dq, dk, dv, dkbar, dvbar)`` from ONE call; q pre-scaled, lse and
    delta [bh, T] float32."""
    bh, T, D = q.shape
    M = kbar.shape[1]
    num_w, num_q, num_k = T // window, window // block_q, window // block_k

    def q_block(w, i, j):
        # a block above the diagonal keeps the first computed one
        return w * num_q + jnp.maximum(j, (i * block_k) // block_q)

    q_spec = pl.BlockSpec((1, block_q, D),
                          lambda b, w, i, j: (b, q_block(w, i, j), 0))
    row_spec = pl.BlockSpec((1, 1, block_q),
                            lambda b, w, i, j: (b, 0, q_block(w, i, j)))
    k_spec = pl.BlockSpec((1, block_k, D),
                          lambda b, w, i, j: (b, w * num_k + i, 0))
    bar_spec = pl.BlockSpec((1, M, D), lambda b, w, i, j: (b, 0, 0))
    with elem():
        lse, delta = lse[:, None, :], delta[:, None, :]
    return _named_call(
        "flash_eva_bwd",
        functools.partial(_bwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, num_w=num_w, num_k=num_k,
                          num_q=num_q, per_window=per_window),
        grid=(bh, num_w, num_k, num_q),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec,
                  bar_spec, bar_spec],
        out_specs=[pl.BlockSpec((1, window, D),
                                lambda b, w, i, j: (b, w, 0)),
                   k_spec, k_spec, bar_spec, bar_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(kbar.shape, kbar.dtype),
                   jax.ShapeDtypeStruct(vbar.shape, vbar.dtype)],
        scratch_shapes=[pltpu.VMEM((window, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((M, D), jnp.float32),
                        pltpu.VMEM((M, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_FUSED_VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, do, lse, delta, kbar, vbar)


# ----------------------------------------------------------- the plain form
def _jnp_fwd(q, k, v, kbar, vbar, scale, window, per_window):
    """The same softmax as a dense masked one a window: ``(o, lse)``."""
    bh, T, D = q.shape
    n, M, f32 = T // window, kbar.shape[1], jnp.float32
    qw = q.reshape(bh, n, window, D)
    own = jnp.einsum("bwqd,bwkd->bwqk", qw, k.reshape(bh, n, window, D),
                     preferred_element_type=f32) * scale
    pos = jnp.arange(window)
    own = jnp.where(pos[:, None] >= pos[None, :], own, _NEG)
    far = jnp.einsum("bwqd,bmd->bwqm", qw, kbar,
                     preferred_element_type=f32) * scale
    seen = jnp.arange(M)[None, :] < per_window * jnp.arange(n)[:, None]
    far = jnp.where(seen[None, :, None, :], far, _NEG)
    s = jnp.concatenate([own, far], axis=-1)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = (jnp.einsum("bwqk,bwkd->bwqd", p[..., :window].astype(v.dtype),
                    v.reshape(bh, n, window, D), preferred_element_type=f32)
         + jnp.einsum("bwqm,bmd->bwqd", p[..., window:].astype(v.dtype), vbar,
                      preferred_element_type=f32))
    return o.reshape(bh, T, D).astype(q.dtype), lse.reshape(bh, T)


def _forward(q, k, v, kbar, vbar, scale, window, per_window, blocks, path):
    if path == "jnp":
        with jax.named_scope("flash_eva_fwd"):
            return _jnp_fwd(q, k, v, kbar, vbar, scale, window, per_window)
    return _fwd_call(q, k, v, kbar, vbar, scale, window, per_window,
                     blocks[0], blocks[1], path == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _eva(q, k, v, kbar, vbar, scale, window, per_window, blocks, path):
    return _forward(q, k, v, kbar, vbar, scale, window, per_window, blocks,
                    path)[0]


def _eva_fwd(q, k, v, kbar, vbar, scale, window, per_window, blocks, path):
    o, lse = _forward(q, k, v, kbar, vbar, scale, window, per_window, blocks,
                      path)
    # the same remat seam as ``flash_attention._flash_fwd``'s
    with elem():
        o = checkpoint_name(o, "flash_out")
        lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, kbar, vbar, o, lse)


def _eva_bwd(scale, window, per_window, blocks, path, res, do):
    from .. import metrics

    q, k, v, kbar, vbar, o, lse = res
    metrics.counter("attention.eva_bwd_traced", _labels(
        window, window // per_window, path)).inc()
    if path == "jnp":
        with jax.named_scope("flash_eva_bwd"):
            _, pull = jax.vjp(
                lambda *a: _jnp_fwd(*a, scale, window, per_window)[0],
                q, k, v, kbar, vbar)
            return pull(do)
    with elem():
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    return _bwd_call(q, k, v, kbar, vbar, do, lse, delta, scale, window,
                     per_window, blocks[2], blocks[3], path == "interpret")


_eva.defvjp(_eva_fwd, _eva_bwd)


def _labels(window, chunk, path):
    return {"window": str(window), "chunk": str(chunk), "path": path}


def eva_attention(q, k, v, kbar, vbar, window: int, chunk: int,
                  scale: Optional[float] = None,
                  block_q: int = 512, block_k: int = 512,
                  block_q_bwd: int = 512, block_k_bwd: int = 512):
    """q, k, v ``[B, H, T, D]``, the chunk summaries kbar, vbar ``[B, H, T //
    chunk, D]`` (``summarise``) → ``[B, H, T, D]``: the module docstring's
    softmax.  ``T`` divides into windows of ``window`` (a shorter sequence is
    one window) and the window into chunks.  Differentiable in all five
    (``jax.custom_vjp``).  Blocks shrink to divide the window; the four block
    sizes are the tests' to set (``flash_attention``'s names): at toy lengths
    they are what gives a window several q and k blocks, unlike ones a pass,
    which the model's call never asks for."""
    from .. import metrics

    B, H, T, D = q.shape
    if (k.shape != q.shape or v.shape != q.shape
            or kbar.shape != (B, H, T // chunk, D)
            or vbar.shape != kbar.shape):
        raise ValueError(
            "eva_attention wants q/k/v [B,H,T,D] and kbar/vbar "
            f"[B,H,T//{chunk},D]; got {q.shape}, {k.shape}, {v.shape}, "
            f"{kbar.shape}, {vbar.shape}")
    if scale is None:
        scale = D ** -0.5
    window, per_window, block_q, block_k = _geometry(T, window, chunk,
                                                     block_q, block_k)
    blocks = (block_q, block_k, fit_block(block_q_bwd, window),
              fit_block(block_k_bwd, window))
    path = kernel_path()
    metrics.counter("attention.eva_traced",
                    _labels(window, chunk, path)).inc()
    if path != "jnp":
        group = _walk_group(per_window, T // window)
        metrics.counter("attention.eva_summary_walk_traced", {
            "width": str(group * per_window), "bodies": str(group)}).inc()
    with elem():
        flat = [x.reshape(B * H, x.shape[2], D) for x in (q, k, v, kbar, vbar)]
    o = _eva(*flat, float(scale), int(window), int(per_window),
             tuple(int(b) for b in blocks), path)
    with elem():
        return o.reshape(B, H, T, D)
