"""Kimi Delta Attention (arXiv:2510.26692) as a chunked scan: a gated delta
rule whose decay is a vector a head and token, its state ``[d_k, d_v]`` a
head carried from chunk to chunk.

The recurrence, a head (``alpha_t = exp(g_t)`` a channel of the key, ``g_t
<= 0``; ``beta_t`` a scalar; the state zero before the first token)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

**Chunks** of ``CHUNK`` = 64 tokens (the paper's section 3, the WY / UT
form).  With ``G`` the cumulative log-decay inside a chunk and ``S`` the state
that enters it, the pseudo-values ``u_t = beta_t (v_t - (Diag(alpha_t)
S_{t-1})^T k_t)`` solve one unit lower-triangular system a chunk::

    A[t, i] = beta_t sum_d k_t[d] k_i[d] exp(G_t[d] - G_i[d])      i <  t
    Aqk[t, i] =      sum_d q_t[d] k_i[d] exp(G_t[d] - G_i[d])      i <= t
    T = (I + A)^-1
    W = T (beta k exp(G))        U0 = T (beta v)        U = U0 - W S
    O = (q exp(G)) S + Aqk U
    S' = Diag(exp(G_last)) S + (k exp(G_last - G))^T U

Everything but ``U``, ``O`` and ``S'`` is a chunk's own (``_intra``); those
three are the scan.  **Decays enter only as differences of cumulative
log-decays, and no quotient of two exponentials spans more than ``SUB`` = 16
tokens**: a row of sub-block ``I`` carries ``exp(G_t - G_ref(I))`` (at most
1) and a column ``exp(G_ref(I) - G_i)``, ``ref(I)`` the sub-block's first
token, which is at most 1 for the columns before the sub-block and at most
``exp(15 |g|)`` inside it: with the bounded gate's ``g >= -5`` that is
``e^75``, inside float32 and bfloat16.  The columns behind the diagonal are
masked; their exponent is clamped so that what is masked is finite.  ``T``
is built from the 16 x 16 diagonal blocks' inverses (a nilpotent series of
four factors) and a block-level series of two factors, ten products in
float32 at "highest", exact but for rounding (``_tri_inv_impl``: the ``jnp``
path's and the tests' plain form, ten 64 x 64 products a chunk and head).
**The forward kernel issues them two heads a 128-wide MXU tile**
(``_tri_inv_packed``): the pair's matrices side by side as the left operand
``[X1 | X2]`` against the block diagonal of ``[Y1 | Y2]`` give ``[X1 Y1 | X2
Y2]``, and the series of the diagonal blocks, which is block-diagonal in 16s,
runs in 16 rows (``pc[r, 16 b + c] = p[16 b + r, 16 b + c]``): six 16-row
and four 64-row products a pair of heads where the plain form has twenty of
64 rows.  Every term the packing adds is an exact zero, so each entry of
``T`` is the sum it is in the plain form.  A grid step with an odd number of
heads solves its lone head through the same helper in a 64-wide tile;
the call counts which in ``attention.linear_solve_traced{heads=,pairs=,
lone=}``, one a trace of the forward kernel.

**Precision**: matmul operands in the inputs' dtype (bfloat16 on the chip),
accumulation in float32; ``g``, the cumulative sums, every exponential, the
solve and the carried state in float32.

**Forward**: one Pallas kernel ``kda_fwd`` (scope and ``pallas_call(name=)``),
grid ``(batch, head group, chunk)`` with the chunk axis sequential and the
state in VMEM scratch; it reads ``q``, ``k``, ``beta k``, ``beta v``, ``g`` in
the model's ``[B, T, H * d]`` layout through its index maps (no transpose) and
writes ``o``, the state that ENTERED each chunk (float32 ``[B, H, chunks,
d_v, d_k]``: 0.5 GiB a layer at 16,384 tokens and 32 heads of 128 x 128) and
the chunk's solve ``T`` (float32 ``[B, H, chunks, C, C]``, 128 MiB there),
which is all the backward keeps beside the inputs.  Off the TPU the same
arithmetic runs as ``jax.numpy`` (``_intra`` batched over the chunks,
``lax.scan`` over them), or the kernels in interpret mode under
``MVTPU_FORCE_FLASH`` (the flash kernels' switch; ``MVTPU_NO_FLASH`` keeps
the kernels off a TPU too).

**Backward**: one Pallas kernel ``kda_bwd`` (scope and name) on the forward's
grid and layout, the chunk axis REVERSED through the index maps.  A grid step
rebuilds the chunk's own part around the kept ``T`` exactly as the forward
built it (``_chunk_own`` and ``_chunk_solved``: the same roundings; the
solve is not run again), rebuilds ``U`` from the kept state, takes
``_scan_bwd``'s step with the state's cotangent in float32 VMEM scratch
(zeroed at the last chunk), and pulls the cotangents of ``W``, ``U0``,
``Aqk`` and the decay factors back through the chunk's own part in place:
the solve's transpose ``-T^T dT T^T`` in float32, the sub-blocks' products,
every exponential's factor back to ``G``, and ``G``'s reverse cumulative sum
back to ``g`` (one float32 product with the upper triangle).  ``dq``, ``dk``, ``dv`` leave in the inputs' dtype, ``dg`` and
``dbeta`` (a lane reduction a token) in float32; nothing between two
products touches HBM.  On the ``jnp`` path the same arithmetic is
``_scan_bwd`` and ``jax.vjp(_intra)``, all heads at once: the tests' plain
form.

``kda`` counts a trace in ``attention.linear_traced{heads=,chunk=,path=}``,
its backward one in ``attention.linear_bwd_traced`` under the same labels,
the forward kernel's solves in ``attention.linear_solve_traced`` (above).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel_path import kernel_path

__all__ = ["kda", "CHUNK", "SUB"]

CHUNK = 64
SUB = 16
# The exponent of a masked column's factor is cut here: exp(80) is finite in
# float32 and bfloat16, and no kept column's exponent passes 15 |g|.
_CLAMP = 80.0
# A kernel's grid step runs this many heads' chunks.
_HEADS = 4
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))          # x @ y^T
_TN = (((0,), (0,)), ((), ()))          # x^T @ y


# ------------------------------------------------------------ a chunk's own
def _masks(n: int):
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return row, col


def _tri_inv_impl(a, mm):
    """``(I + a)^-1`` of strictly lower triangular ``a [..., C, C]``
    (float32), ``mm(x, y)`` the float32 product (module docstring)."""
    n = a.shape[-1]
    row, col = _masks(n)
    eye = (row == col).astype(a.dtype)
    same = (row // SUB) == (col // SUB)
    p = -jnp.where(same, a, 0.0)             # the diagonal blocks, negated
    d_inv = eye + p
    for _ in range(SUB.bit_length() - 2):    # (I+p)(I+p^2)(I+p^4)(I+p^8)
        p = mm(p, p)
        d_inv = d_inv + mm(d_inv, p)
    x = mm(d_inv, jnp.where(same, 0.0, a))   # block strictly lower
    y, p = eye - x, mm(x, x)
    for j in range((n // SUB).bit_length() - 2):
        y = y + mm(y, p)
        if j < (n // SUB).bit_length() - 3:
            p = mm(p, p)
    return mm(y, d_inv)


def _mm32(x, y):
    return jnp.matmul(x, y, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def _tri_inv(a):
    return _tri_inv_impl(a, _mm32)


def _tri_inv_fwd(a):
    t = _tri_inv(a)
    return t, t


def _tri_inv_bwd(t, d_t):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm32(_mm32(tt, d_t), tt),)


_tri_inv.defvjp(_tri_inv_fwd, _tri_inv_bwd)


def _intra(q, k, v, g, beta):
    """What a chunk computes without the state, every chunk at once: ``q``,
    ``k``, ``g [..., C, d_k]``, ``v [..., C, d_v]``, ``beta [..., C]`` →
    ``(W [..., C, d_k] f32, U0 [..., C, d_v] f32, Aqk [..., C, C] f32, q
    exp(G), k exp(G_last - G) in the inputs' dtype, exp(G_last) [..., d_k]
    f32)``."""
    f32, dt = jnp.float32, q.dtype
    C = q.shape[-2]
    qf, kf = q.astype(f32), k.astype(f32)
    G = jnp.cumsum(g.astype(f32), axis=-2)
    firsts = G[..., ::SUB, :]                                # [..., C/SUB, d]
    rowf = jnp.exp(G - jnp.repeat(firsts, SUB, axis=-2))
    kb = beta.astype(f32)[..., None] * kf
    # [..., I, C, d]: the columns as row block I reads them
    colf = jnp.exp(jnp.minimum(firsts[..., :, None, :] - G[..., None, :, :],
                               _CLAMP))
    kc = (kf[..., None, :, :] * colf).astype(dt)
    blocks = lambda x: (x * rowf).astype(dt).reshape(
        *x.shape[:-2], C // SUB, SUB, x.shape[-1])
    a = jnp.einsum("...ird,...icd->...irc", blocks(kb), kc,
                   preferred_element_type=f32).reshape(*q.shape[:-2], C, C)
    aqk = jnp.einsum("...ird,...icd->...irc", blocks(qf), kc,
                     preferred_element_type=f32).reshape(*q.shape[:-2], C, C)
    row, col = _masks(C)
    aqk = jnp.where(row >= col, aqk, 0.0)
    t = _tri_inv(jnp.where(row > col, a, 0.0)).astype(dt)
    eg = jnp.exp(G)
    w = jnp.matmul(t, (kb * eg).astype(dt), preferred_element_type=f32)
    u0 = jnp.matmul(t, (beta.astype(f32)[..., None] * v.astype(f32)
                        ).astype(dt), preferred_element_type=f32)
    last = G[..., -1:, :]
    return (w, u0, aqk, (qf * eg).astype(dt),
            (kf * jnp.exp(last - G)).astype(dt), jnp.exp(last[..., 0, :]))


# ------------------------------------------------------------------ the scan
def _ein(spec, x, y):
    return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)


def _scan_fwd(w, u0, aqk, qg, kd, ec):
    """The chunks in turn, leading axes ``[N, ...]`` with ``N`` the chunks:
    ``(O [N, ..., C, d_v] f32, the state entering each chunk [N, ..., d_v,
    d_k] f32)``."""
    dt = qg.dtype

    def step(s, xs):
        w, u0, aqk, qg, kd, ec = xs
        sb = s.astype(dt)
        u = (u0 - _ein("...cd,...vd->...cv", w.astype(dt), sb)).astype(dt)
        o = (_ein("...cd,...vd->...cv", qg, sb)
             + _ein("...ct,...tv->...cv", aqk.astype(dt), u))
        s_next = s * ec[..., None, :] + _ein("...cv,...cd->...vd", u, kd)
        return s_next, (o, s)

    s0 = jnp.zeros((*u0.shape[1:-2], u0.shape[-1], w.shape[-1]), jnp.float32)
    _, (o, states) = jax.lax.scan(step, s0, (w, u0, aqk, qg, kd, ec))
    return o, states


def _scan_bwd(parts, states, d_o):
    """The cotangents of ``_intra``'s outputs from ``d_o [N, ..., C, d_v]``
    and the states the forward kept."""
    w, u0, aqk, qg, kd, ec = parts
    dt = qg.dtype
    sb = states.astype(dt)
    u = (u0 - _ein("n...cd,n...vd->n...cv", w.astype(dt), sb)).astype(dt)
    d_o = d_o.astype(dt)
    aqk_b, w_b = aqk.astype(dt), w.astype(dt)

    def step(d_s, xs):
        aqk, d_o, kd, qg, ec, w, u, s = xs
        d_sb = d_s.astype(dt)
        d_u = (_ein("...ct,...cv->...tv", aqk, d_o)
               + _ein("...cd,...vd->...cv", kd, d_sb))
        d_kd = _ein("...cv,...vd->...cd", u, d_sb)
        d_ec = jnp.sum(s * d_s, axis=-2)
        d_s = (d_s * ec[..., None, :] + _ein("...cv,...cd->...vd", d_o, qg)
               - _ein("...cv,...cd->...vd", d_u.astype(dt), w))
        return d_s, (d_u, d_kd, d_ec)

    _, (d_u, d_kd, d_ec) = jax.lax.scan(
        step, jnp.zeros(states.shape[1:], jnp.float32),
        (aqk_b, d_o, kd, qg, ec, w_b, u, states), reverse=True)
    d_w = -_ein("n...cv,n...vd->n...cd", d_u.astype(dt), sb)
    d_qg = _ein("n...cv,n...vd->n...cd", d_o, sb)
    d_aqk = _ein("n...cv,n...tv->n...ct", d_o, u)
    return d_w, d_u, d_aqk, d_qg.astype(dt), d_kd.astype(dt), d_ec


def _chunked(x, n):
    """``[B, T, H, ...]`` → ``[n, B, H, C, ...]``."""
    B, T, H = x.shape[:3]
    x = x.reshape(B, n, T // n, H, *x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def _unchunked(x):
    """``[n, B, H, C, d]`` → ``[B, n * C, H, d]``."""
    n, B, H, C, d = x.shape
    return jnp.moveaxis(jnp.moveaxis(x, 0, 1), 2, 3).reshape(B, n * C, H, d)


def _fwd_jnp(q, k, v, g, beta):
    n = q.shape[1] // CHUNK
    o, states = _scan_fwd(*_intra(*(_chunked(x, n)
                                    for x in (q, k, v, g, beta))))
    return _unchunked(o).astype(v.dtype), states


# ---------------------------------------------------------------- the kernel
def _mm(x, y, dims=(((1,), (0,)), ((), ())), precision=None):
    return jax.lax.dot_general(x, y, dims, precision=precision,
                               preferred_element_type=jnp.float32)


_mm_f32 = functools.partial(_mm, precision=_HIGHEST)


def _block_diag(x, size):
    """``x [r, W]`` tiled down the rows to ``[W, W]`` and cut to its diagonal
    blocks of ``size``: the right operand under which a packed left operand
    ``[X1 | X2 | ...]`` gives ``[X1 Y1 | X2 Y2 | ...]``, the zeros exact in
    every partial sum."""
    W = x.shape[1]
    if x.shape[0] == size == W:
        return x
    row, col = _masks(W)
    return jnp.where(row // size == col // size,
                     jnp.concatenate([x] * (W // x.shape[0]), axis=0), 0.0)


def _tri_inv_tile(mats):
    """``_tri_inv_impl``'s products for one or two matrices side by side in
    one MXU tile, ``[C, C]`` or ``[C, 2 C]`` from ``A`` to ``T``: the same
    sums entry by entry (module docstring)."""
    C = mats[0].shape[0]
    a = mats[0] if len(mats) == 1 else jnp.concatenate(mats, axis=1)
    W = a.shape[1]
    f32 = a.dtype

    def places(n):              # a row, and a column's place in its own matrix
        return (jax.lax.broadcasted_iota(jnp.int32, (n, W), 0),
                jax.lax.broadcasted_iota(jnp.int32, (n, W), 1) % C)

    (row, own), (row_c, own_c) = places(C), places(SUB)
    same = (row // SUB) == (own // SUB)
    # the diagonal blocks, negated, in SUB rows: pc[r, SUB b + c] =
    # p[SUB b + r, SUB b + c], a column's one block picked out of the rows'
    pc = -a[:SUB]
    for b in range(1, C // SUB):
        pc = jnp.where(own_c // SUB == b, -a[b * SUB:(b + 1) * SUB], pc)
    d = (row_c == own_c % SUB).astype(f32) + pc
    pb = _block_diag(pc, SUB)
    for _ in range(SUB.bit_length() - 2):    # (I+p)(I+p^2)(I+p^4)(I+p^8)
        pc = _mm_f32(pc, pb)
        pb = _block_diag(pc, SUB)
        d = d + _mm_f32(d, pb)
    d_inv = jnp.where(same, jnp.concatenate([d] * (C // SUB), axis=0), 0.0)
    x = _mm_f32(d_inv, _block_diag(jnp.where(same, 0.0, a), C))
    y, p = (row == own).astype(f32) - x, _mm_f32(x, _block_diag(x, C))
    for j in range((C // SUB).bit_length() - 2):
        y = y + _mm_f32(y, _block_diag(p, C))
        if j < (C // SUB).bit_length() - 3:
            p = _mm_f32(p, _block_diag(p, C))
    t = _mm_f32(y, _block_diag(d_inv, C))
    return [t[:, i * C:(i + 1) * C] for i in range(len(mats))]


def _tri_inv_packed(mats):
    """``(I + a)^-1`` of every strictly lower triangular ``a [C, C]``
    (float32) of ``mats``, two a tile and an odd one out alone."""
    return [t for i in range(0, len(mats), 2)
            for t in _tri_inv_tile(mats[i:i + 2])]


def _chunk_own(q, k, kb, g, dt, solve):
    """What a chunk computes without the state up to its solve, one head in a
    kernel (``q``, ``k``, ``kb = beta k`` float32 ``[C, d_k]``, ``g``
    float32): ``_intra``'s arithmetic with the sub-blocks' products unrolled.
    Both kernels build it here and finish it in ``_chunk_solved``, so the
    backward rebuilds the forward's roundings exactly; only the forward asks
    for the masked ``A`` that it will ``solve``."""
    C, dk = q.shape
    row, col = _masks(C)
    G = _mm_f32((row >= col).astype(jnp.float32), g)          # cumulative
    firsts = [G[i:i + 1] for i in range(0, C, SUB)]
    rowf = jnp.exp(G - jnp.concatenate(
        [jnp.broadcast_to(f, (SUB, dk)) for f in firsts], axis=0))
    kr, qr = (kb * rowf).astype(dt), (q * rowf).astype(dt)
    colf = [jnp.exp(jnp.minimum(first - G, _CLAMP)) for first in firsts]
    kc = [(k * f).astype(dt) for f in colf]
    blocks = [slice(i * SUB, (i + 1) * SUB) for i in range(C // SUB)]
    own = dict(G=G, rowf=rowf, kr=kr, qr=qr, colf=colf, kc=kc)
    own["aqk"] = jnp.where(row >= col, jnp.concatenate(
        [_mm(qr[rows], c, _NT) for rows, c in zip(blocks, kc)], axis=0), 0.0)
    if solve:
        own["a"] = jnp.where(row > col, jnp.concatenate(
            [_mm(kr[rows], c, _NT) for rows, c in zip(blocks, kc)], axis=0),
            0.0)
    own["eg"] = jnp.exp(G)
    own["kbe"] = (kb * own["eg"]).astype(dt)
    return own


def _chunk_solved(own, t, vb, dt):
    """``own`` with what follows the solve ``t`` (the forward's own, or the
    one it kept for the backward): ``T`` in ``dt``, ``W`` and ``U0``
    (``vb = beta v [C, d_v]`` in ``dt``)."""
    tb = t.astype(dt)
    return dict(own, t=t, tb=tb, w=_mm(tb, own["kbe"]), u0=_mm(tb, vb))


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, s_ref, t_ref,
                state, *, heads, dk, dv):
    """One chunk of ``heads`` heads (their columns side by side in the
    blocks): every head's ``A`` first, the solves two heads a tile
    (``_tri_inv_packed``), then each head's step of the scan; the heads'
    chains of products are independent, so the scheduler fills one's
    latencies with another's."""
    f32, dt = jnp.float32, q_ref.dtype
    C = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[:] = jnp.zeros_like(state)

    at_k = [slice(h * dk, (h + 1) * dk) for h in range(heads)]
    at_v = [slice(h * dv, (h + 1) * dv) for h in range(heads)]
    qs, ks = ([r[0, :, at].astype(f32) for at in at_k]
              for r in (q_ref, k_ref))
    owns = [_chunk_own(q, k, kb_ref[0, :, at].astype(f32), g_ref[0, :, at],
                       dt, solve=True) for q, k, at in zip(qs, ks, at_k)]
    solves = _tri_inv_packed([own["a"] for own in owns])
    for h, (q, k, own, t) in enumerate(zip(qs, ks, owns, solves)):
        own = _chunk_solved(own, t, vb_ref[0, :, at_v[h]], dt)
        G, eg = own["G"], own["eg"]
        t_ref[0, h, 0] = t
        s = state[h]                                          # [d_v, d_k]
        s_ref[0, h, 0] = s
        sb = s.astype(dt)
        u = (own["u0"] - _mm(own["w"].astype(dt), sb, _NT)).astype(dt)
        o_ref[0, :, at_v[h]] = (_mm((q * eg).astype(dt), sb, _NT)
                                + _mm(own["aqk"].astype(dt), u)
                                ).astype(o_ref.dtype)
        last = G[C - 1:C]
        state[h] = s * jnp.exp(last) + _mm(
            u, (k * jnp.exp(last - G)).astype(dt), _TN)


def _a_chunk(hb, rows, cols, at):
    """The block of a ``[B, H, chunks, rows, cols]`` array that holds chunk
    ``at(c)`` of a grid step's ``hb`` heads."""
    return pl.BlockSpec((1, hb, 1, rows, cols),
                        lambda b, h, c: (b, h, at(c), 0, 0))


def _fwd_kernel_call(q, k, v, g, beta, interpret):
    """``(o, (states, solves))``: what entered each chunk and each chunk's
    ``T``, float32 ``[B, H, chunks, d_v, d_k]`` and ``[..., C, C]``."""
    from .. import metrics

    B, T, H, dk = q.shape
    dv = v.shape[-1]
    n = T // CHUNK
    f32 = jnp.float32
    hb = math.gcd(H, _HEADS)
    metrics.counter("attention.linear_solve_traced",
                    {"heads": str(H), "pairs": str(hb // 2),
                     "lone": str(hb % 2)}).inc()
    bf = beta.astype(f32)[..., None]
    kb = (bf * k.astype(f32)).astype(k.dtype)
    vb = (bf * v.astype(f32)).astype(v.dtype)

    def spec(width):
        return pl.BlockSpec((1, CHUNK, hb * width), lambda b, h, c: (b, c, h))

    call = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hb, dk=dk, dv=dv),
        name="kda_fwd", grid=(B, H // hb, n),
        in_specs=[spec(dk), spec(dk), spec(dk), spec(dv), spec(dk)],
        out_specs=[spec(dv), _a_chunk(hb, dv, dk, lambda c: c),
                   _a_chunk(hb, CHUNK, CHUNK, lambda c: c)],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * dv), v.dtype),
                   jax.ShapeDtypeStruct((B, H, n, dv, dk), f32),
                   jax.ShapeDtypeStruct((B, H, n, CHUNK, CHUNK), f32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)
    flat = lambda x: x.reshape(B, T, H * x.shape[-1])
    o, states, solves = call(flat(q), flat(k), flat(kb), flat(vb),
                             flat(g.astype(f32)))
    return o.reshape(B, T, H, dv), (states, solves)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, s_ref, t_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, d_state,
                *, heads, dk, dv):
    """One chunk of ``heads`` heads, the chunks LAST TO FIRST (the index
    maps reverse the axis): rebuild the chunk's own part as the forward
    built it around the ``T`` it kept, ``_scan_bwd``'s step with the state's
    cotangent in ``d_state`` (float32 VMEM), then the pull back through the
    chunk's own part that ``jax.vjp(_intra)`` is on the ``jnp`` path.
    Cotangents stay float32 between products; a product's operands are the
    inputs' dtype, and two products that share an operand are one, their
    other operands side by side."""
    f32, dt = jnp.float32, q_ref.dtype
    C = q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        d_state[:] = jnp.zeros_like(d_state)

    row, col = _masks(C)
    row_k = jax.lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    # G's cotangent and the sub-blocks' first rows' back to g in one product:
    # [upper | upper at a column's sub-block's first row] [C, 2 C]
    wide = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 1)
    row2 = jax.lax.broadcasted_iota(jnp.int32, (C, 2 * C), 0)
    back = (row2 <= jnp.where(wide < C, wide, (wide - C) // SUB * SUB)
            ).astype(f32)
    betas = beta_ref[0, 0]                                    # [C, heads]
    lane = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    d_betas = jnp.zeros(betas.shape, f32)
    for h in range(heads):
        at_k, at_v = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        q, k = (r[0, :, at_k].astype(f32) for r in (q_ref, k_ref))
        v = v_ref[0, :, at_v].astype(f32)
        beta = betas[:, h:h + 1]                              # [C, 1]
        kb = (beta * k).astype(dt).astype(f32)                # as the forward
        vb = (beta * v).astype(dt)
        own = _chunk_solved(
            _chunk_own(q, k, kb, g_ref[0, :, at_k], dt, solve=False),
            t_ref[0, h, 0], vb, dt)
        G, rowf, eg, t = own["G"], own["rowf"], own["eg"], own["t"]
        s = s_ref[0, h, 0]                                    # [d_v, d_k]
        sb, wb = s.astype(dt), own["w"].astype(dt)
        u = (own["u0"] - _mm(wb, sb, _NT)).astype(dt)
        qe = (q * eg).astype(dt)
        last = G[C - 1:C]
        ec, x = jnp.exp(last), jnp.exp(last - G)
        kd = (k * x).astype(dt)
        d_o = do_ref[0, :, at_v].astype(dt)
        # ---- the scan's step (``_scan_bwd``)
        d_s = d_state[h]
        d_sb = d_s.astype(dt)
        d_u = (_mm(own["aqk"].astype(dt), d_o, _TN)
               + _mm(kd, d_sb, _NT)).astype(dt)
        d_kd = _mm(u, d_sb).astype(dt).astype(f32)
        d_last = jnp.sum(s * d_s, axis=0, keepdims=True) * ec
        d_state[h] = d_s * ec + _mm(jnp.concatenate([d_o, d_u], axis=0),
                                    jnp.concatenate([qe, -wb], axis=0), _TN)
        d_wq = _mm(jnp.concatenate([d_u, d_o], axis=0), sb)   # [2 C, d_k]
        d_w, d_qe = (-d_wq[:C]).astype(dt), d_wq[C:].astype(dt).astype(f32)
        d_aqk = jnp.where(row >= col, _mm(d_o, u, _NT), 0.0)
        # ---- back through W = T (beta k exp G), U0 = T (beta v), the solve
        d_wu = jnp.concatenate([d_w, d_u], axis=1)            # [C, d_k + d_v]
        d_t = _mm(d_wu, jnp.concatenate([own["kbe"], vb], axis=1), _NT)
        d_kv = _mm(own["tb"], d_wu, _TN)
        d_kbe, d_vb = d_kv[:, :dk], d_kv[:, dk:]
        d_a = jnp.where(row > col,
                        -_mm_f32(_mm_f32(t, d_t, _TN), t, _NT), 0.0)
        # ---- back through the sub-blocks' products
        d_kqr, d_k, d_G = [], 0.0, 0.0
        for i in range(C // SUB):
            rows = slice(i * SUB, (i + 1) * SUB)
            both = jnp.concatenate([d_a[rows], d_aqk[rows]], axis=0
                                   ).astype(dt)               # [2 SUB, C]
            d_kqr.append(_mm(both, own["kc"][i]))
            d_kc = _mm(both, jnp.concatenate(
                [own["kr"][rows], own["qr"][rows]], axis=0), _TN)
            colf = own["colf"][i]
            d_k = d_k + d_kc * colf
            d_e = jnp.where(G[i * SUB:i * SUB + 1] - G < _CLAMP,
                            d_kc * k * colf, 0.0)
            d_G = d_G - d_e + jnp.where(
                row_k == i * SUB, jnp.sum(d_e, axis=0, keepdims=True), 0.0)
        d_kr, d_qr = (jnp.concatenate([x[part] for x in d_kqr], axis=0)
                      for part in (slice(0, SUB), slice(SUB, 2 * SUB)))
        # ---- every exponential's factor back to G, G back to g
        d_kb = d_kr * rowf + d_kbe * eg
        d_d = (d_kr * kb + d_qr * q) * rowf                   # of G - firsts
        d_x = d_kd * k * x
        d_G = d_G + d_d + (d_kbe * kb + d_qe * q) * eg - d_x + jnp.where(
            row_k == C - 1, jnp.sum(d_x, axis=0, keepdims=True) + d_last,
            0.0)
        dg_ref[0, :, at_k] = _mm_f32(
            back, jnp.concatenate([d_G, -d_d], axis=0))
        dq_ref[0, :, at_k] = (d_qr * rowf + d_qe * eg).astype(dq_ref.dtype)
        dk_ref[0, :, at_k] = (d_k + d_kd * x + beta * d_kb
                              ).astype(dk_ref.dtype)
        dv_ref[0, :, at_v] = (beta * d_vb).astype(dv_ref.dtype)
        d_betas = jnp.where(
            lane == h, jnp.sum(d_kb * k, axis=1, keepdims=True)
            + jnp.sum(d_vb * v, axis=1, keepdims=True), d_betas)
    dbeta_ref[0, 0] = d_betas


def _bwd_kernel_call(q, k, v, g, beta, kept, d_o, interpret):
    """``(dq, dk, dv, dg, dbeta)`` from the inputs, what the forward kernel
    kept (``_fwd_kernel_call``) and ``d_o``."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    n = T // CHUNK
    f32 = jnp.float32
    hb = math.gcd(H, _HEADS)

    def spec(width):
        return pl.BlockSpec((1, CHUNK, hb * width),
                            lambda b, h, c: (b, n - 1 - c, h))

    # beta and its gradient a head group apart, [B, H / hb, T, hb]: a block's
    # last dimension is the array's own
    a_token = pl.BlockSpec((1, 1, CHUNK, hb),
                           lambda b, h, c: (b, h, n - 1 - c, 0))
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hb, dk=dk, dv=dv),
        name="kda_bwd", grid=(B, H // hb, n),
        in_specs=[spec(dk), spec(dk), spec(dv), spec(dk), a_token, spec(dv),
                  _a_chunk(hb, dv, dk, lambda c: n - 1 - c),
                  _a_chunk(hb, CHUNK, CHUNK, lambda c: n - 1 - c)],
        out_specs=[spec(dk), spec(dk), spec(dv), spec(dk), a_token],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * dk), q.dtype),
                   jax.ShapeDtypeStruct((B, T, H * dk), k.dtype),
                   jax.ShapeDtypeStruct((B, T, H * dv), v.dtype),
                   jax.ShapeDtypeStruct((B, T, H * dk), f32),
                   jax.ShapeDtypeStruct((B, H // hb, T, hb), f32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)
    flat = lambda x: x.reshape(B, T, H * x.shape[-1])
    grouped = lambda x: jnp.moveaxis(x.reshape(B, T, H // hb, hb), 2, 1)
    dq, d_k, d_v, dg, dbeta = call(
        flat(q), flat(k), flat(v), flat(g.astype(f32)),
        grouped(beta.astype(f32)), flat(d_o), *kept)
    return (dq.reshape(q.shape), d_k.reshape(k.shape), d_v.reshape(v.shape),
            dg.reshape(g.shape), jnp.moveaxis(dbeta, 1, 2).reshape(B, T, H))


# ------------------------------------------------------------ the custom_vjp
def _forward(q, k, v, g, beta, path):
    """``(o, what the backward keeps)``: the states, and from a kernel the
    solves beside them."""
    with jax.named_scope("kda_fwd"):
        if path == "jnp":
            o, states = _fwd_jnp(q, k, v, g, beta)
            return o, (states,)
        return _fwd_kernel_call(q, k, v, g, beta, path == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, path):
    return _forward(q, k, v, g, beta, path)[0]


def _kda_fwd(q, k, v, g, beta, path):
    # Named so that remat policy "dots" keeps them (``transformer.py``): with
    # o and these saved the backward does not run the forward again.
    o, kept = _forward(q, k, v, g, beta, path)
    kept = tuple(checkpoint_name(x, name)
                 for x, name in zip(kept, ("kda_state", "kda_solve")))
    return checkpoint_name(o, "kda_out"), (q, k, v, g, beta, kept)


def _kda_bwd(path, res, d_o):
    from .. import metrics

    q, k, v, g, beta, kept = res
    metrics.counter("attention.linear_bwd_traced",
                    {"heads": str(q.shape[2]), "chunk": str(CHUNK),
                     "path": path}).inc()
    with jax.named_scope("kda_bwd"):
        if path != "jnp":
            return _bwd_kernel_call(q, k, v, g, beta, kept, d_o,
                                    path == "interpret")
        (states,), n = kept, kept[0].shape[0]
        parts, pull = jax.vjp(_intra, *(_chunked(x, n)
                                        for x in (q, k, v, g, beta)))
        got = pull(_scan_bwd(parts, states, _chunked(d_o, n)))
        return tuple(
            _unchunked(x if x.ndim == 5 else x[..., None]).reshape(
                like.shape).astype(like.dtype)
            for x, like in zip(got, (q, k, v, g, beta)))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q, k, v, g, beta):
    """Kimi Delta Attention over a sequence from a zero state: ``q``, ``k``
    ``[B, T, H, d_k]`` (as the layer made them: normalised, ``q`` scaled),
    ``v [B, T, H, d_v]``, the log-decay ``g [B, T, H, d_k]`` (``<= 0``; kept
    in float32) and ``beta [B, T, H]`` → ``o [B, T, H, d_v]`` in ``v``'s
    dtype.  Differentiable in all five (``jax.custom_vjp``; the module
    docstring has both passes).  A length that no chunk divides is padded
    with tokens that leave the state as it is (``beta`` 0, ``g`` 0)."""
    from .. import metrics

    B, T, H, dk = q.shape
    if (k.shape != q.shape or g.shape != q.shape or v.shape[:3] != (B, T, H)
            or beta.shape != (B, T, H)):
        raise ValueError(
            "kda wants q/k/g [B,T,H,dk], v [B,T,H,dv], beta [B,T,H]; got "
            f"{q.shape}, {k.shape}, {g.shape}, {v.shape}, {beta.shape}")
    path = kernel_path()
    metrics.counter("attention.linear_traced",
                    {"heads": str(H), "chunk": str(CHUNK), "path": path}).inc()
    pad = -T % CHUNK
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    o = _kda(q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32), path)
    return o[:, :T] if pad else o
