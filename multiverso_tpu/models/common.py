"""What every sub-layer of the decoder shares: the rotary recipe and its
application, the RMS norm, and ``Ctx``, the few facts of one trace (dtypes,
mesh, the manual-``tp`` reduction) that ``transformer.py``'s parts and the
attention kinds under ``attention/`` read instead of closing over them.

Imports nothing from ``transformer.py`` or ``attention/``: the arrows point
down (``transformer.py`` -> ``attention/`` -> here -> ``ops/``,
``parallel/``).  A configuration is read by attribute."""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh

from ..ops.kernel_path import elem

__all__ = ["Rope", "Ctx", "AttnKind", "Draw", "proj", "elem", "unit_gain",
           "rms_norm", "head_sum", "head_spread", "rope_freqs", "apply_rope",
           "GATE_ACTS"]

# What squashes a gated FFN's ``w1`` branch, ``act(h w1) * (h w3)``: SwiGLU's
# SiLU, or ReGLU's ReLU (``TransformerConfig.ffn_act``), dense, shared and
# routed FFNs alike.  ReLU's derivative at 0 is ``jax.nn.relu``'s: 0.
GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclass(frozen=True)
class Rope:
    """One rotary recipe.  The first ``rotary_factor`` of every head's dims
    are rotated (split in halves, as ``apply_rope`` always has), the rest pass.
    ``yarn_factor`` > 0 blends interpolated and extrapolated inverse
    frequencies as HF's ``_compute_yarn_parameters`` does (ramp between the
    dims that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_seq`` positions, truncated); cos and sin are multiplied
    by ``attention_factor``."""
    theta: float = 10000.0
    rotary_factor: float = 1.0
    yarn_factor: float = 0.0
    original_max_seq: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def _identity(t):
    return t


@dataclass(frozen=True)
class Ctx:
    """One trace of the model as its sub-layers see it.  ``ring``: attention
    runs inside ``ring_attention``'s ``shard_map`` (any multi-device mesh
    outside a pipeline stage).  ``tp`` / ``red`` specialise a block for manual
    tensor parallelism inside a pipeline stage: it sees tp-local column shards
    (``heads / tp`` heads) and ``red``, a ``psum`` over ``tp``, completes the
    row-parallel ``wo`` / ``w2`` products; by default whole heads and no
    collective of its own."""
    cfg: Any                       # the TransformerConfig
    mesh: Optional[Mesh]
    ring: bool = False
    tp: int = 1
    red: Callable = _identity

    @property
    def dt(self):
        """The compute dtype."""
        return self.cfg.compute_dtype

    @property
    def res_dt(self):
        """The residual stream's dtype; the norms and sub-layers read it in
        ``dt``."""
        return self.cfg.residual_dtype or self.dt

    @property
    def wide(self) -> bool:
        return jnp.dtype(self.res_dt) != jnp.dtype(self.dt)

    def gain(self, leaf):
        """A norm's gain in the compute dtype: the leaf, or ``1 + leaf``."""
        if self.cfg.norm_unit_offset:
            return (leaf.astype(jnp.float32) + 1.0).astype(self.dt)
        return leaf.astype(self.dt)

    def add(self, x, out):
        """The residual sum ``x + out`` in the stream's dtype."""
        return x + (out.astype(self.res_dt) if self.wide else out)

    def read(self, x):
        """The stream as a norm reads it: in the compute dtype."""
        return x.astype(self.dt) if self.wide else x

    def wc(self, w):
        # Named so the "dots" policy SAVES the bf16 weight cast:
        # the cast is not a dot, so without the name the
        # backward re-reads the f32 masters and recasts every
        # big weight per layer — avoidable HBM traffic for one
        # bf16 copy of the layer weights of residency.
        return checkpoint_name(w.astype(self.dt), "wcast")


class AttnKind(NamedTuple):
    """Everything the model knows of one kind of attention
    (``attention.KINDS`` has one a kind); ``kind`` is the layer's
    ``LayerKind`` (``attn``, ``heads``, ``ffn``), ``cfg`` the configuration.

    ``scope``: its ``named_scope`` inside ``attn`` where a configuration's
    layers differ in kind.  ``saved``: what its kernels name for remat policy
    "dots" to keep.  ``gate_tp``: whether the per-head gate ``wg`` shards over
    ``tp`` with its heads.  ``check(cfg, kind)`` raises on a configuration it
    cannot run.  ``init(cfg, kind, w) -> leaves`` draws the attention's own
    leaves, ``wo`` included, ``w`` the layer's ``Draw``.
    ``pspecs(cfg, kind, tp, tp_size) -> {leaf: PartitionSpec}`` of those
    leaves (``tp`` the axis' name or None, ``tp_size`` 1 without one),
    refusing a ``tp`` it has no layout for.  ``refuse(cfg, mesh)`` raises on a mesh it does not run over.
    ``rope(cfg)``: its recipe as given, or None.  ``heads(ctx, kind, h, lyr)
    -> o [B, T, heads, width]`` from the normed input ``h``, or flat ``[B, T,
    heads * width]`` from a kind that keeps that layout throughout
    (``linear.py``); the caller gates in the shape it is given, multiplies by
    ``wo`` and adds the residual."""
    scope: str
    saved: Tuple[str, ...]
    gate_tp: bool
    check: Callable
    init: Callable
    pspecs: Callable
    refuse: Callable
    rope: Callable
    heads: Callable


class Draw:
    """The seeded draws of one layer, or of the leaves outside the layers,
    float32 on the device: a leaf's values are a function of ``key`` (the
    seed's, folded with the layer's index) and the leaf's ``name`` alone,
    whatever else is drawn and in whatever order.  ``w(name, *shape,
    scale=None)`` is a matrix N(0, s^2), ``s = init_std or scale or
    shape[0] ** -0.5``; ``w.normal`` takes its deviation as given; ``w.key``
    is the leaf's key, for a draw that is not a normal."""

    def __init__(self, key, init_std: float = 0.0):
        self._key, self._init_std = key, init_std

    def key(self, name: str):
        return jax.random.fold_in(self._key, zlib.crc32(name.encode()))

    def normal(self, name: str, shape, scale: float):
        # Drawn as a matrix of the last axis' rows: XLA:TPU takes 26 s to
        # compile the draw of [64, 512, 512] and 1 s for [64 * 512, 512].
        rows = (math.prod(shape[:-1]), shape[-1])
        return scale * jax.random.normal(
            self.key(name), rows, jnp.float32).reshape(shape)

    def __call__(self, name: str, *shape, scale=None):
        return self.normal(name, shape,
                           self._init_std or scale or shape[0] ** -0.5)


def proj():
    """The scope ``attn.proj`` (inside ``attn`` and the kind's own): every
    product of the attention sub-layer's normed input, or of a latent's
    normed compression, with a weight that feeds the kernel or a gate, the
    weight's cast with it.  With ``elem`` (``ops/kernel_path.py``, which
    the kernels' wrappers share) and ``transformer.py``'s
    ``attn.out`` it divides what ``attn`` holds around its kernels (the
    calls, and ``attn.eva.summarise``, stay directly under the kind's scope),
    as ``mlp.up`` and ``mlp.down`` divide a dense ``mlp``: name-stack
    metadata alone, read by ``benchmarks/trace/parts.py``."""
    return jax.named_scope("attn.proj")


def unit_gain(cfg, n: int):
    """A norm's gain of one as its leaf holds it: ones, or with
    ``norm_unit_offset`` (the gain is ``1 + w``) zeros."""
    return (np.zeros if cfg.norm_unit_offset else np.ones)(n, np.float32)


def rms_norm(x, gain, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * gain


def _head_of(heads: int, width: int, dtype):
    """The constant 0/1 matrix ``S [heads * width, heads]``, ``S[c, h] = (c //
    width == h)``: which head a channel of a flat ``[..., heads * width]``
    array belongs to."""
    shape = (heads * width, heads)
    channel = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (channel // width
            == jax.lax.broadcasted_iota(jnp.int32, shape, 1)).astype(dtype)


def head_sum(x, heads: int):
    """``x [..., heads * width]`` summed over each head's channels ->
    ``[..., heads]`` without leaving the flat array: a product with
    ``_head_of``'s matrix at "highest", in float32 the sum of
    ``x.reshape(..., heads, width).sum(-1)`` in another order.  On a TPU that
    reshape is a copy of the whole array, and its transpose another in the
    backward (``attention/linear.py`` has why); the product is neither."""
    S = _head_of(heads, x.shape[-1] // heads, x.dtype)
    return jnp.dot(x, S, precision=jax.lax.Precision.HIGHEST)


def head_spread(s, width: int):
    """``s [..., heads]`` -> ``[..., heads * width]``, a head's number
    repeated over its channels: ``head_sum``'s transpose, exact (every sum
    has one term, times one)."""
    S = _head_of(s.shape[-1], width, s.dtype)
    return jnp.dot(s, S.T, precision=jax.lax.Precision.HIGHEST)


def rope_freqs(rope: Rope, half: int):
    """Inverse frequencies ``[half]`` of a recipe that rotates ``2 * half``
    dims."""
    if not rope.yarn_factor:
        return rope.theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # YaRN, as HF's _compute_yarn_parameters: constants of the trace.
    rot = 2 * half
    plain = rope.theta ** (-np.arange(half, dtype=np.float64) / half)

    def turns_at(turns):      # the dim that turns ``turns`` times
        return (rot * math.log(rope.original_max_seq / (turns * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(turns_at(rope.beta_fast)), 0)
    high = min(math.ceil(turns_at(rope.beta_slow)), rot - 1)
    ramp = np.clip((np.arange(half) - low) / (max(high - low, 0.001)), 0, 1)
    extrapolated = 1.0 - ramp
    freqs = (plain / rope.yarn_factor * (1 - extrapolated)
             + plain * extrapolated)
    return jnp.asarray(freqs, jnp.float32)


def apply_rope(x, rope: Rope):
    """Rotary embedding over global positions; x [B, H, T, D]."""
    B, H, T, D = x.shape
    rot = int(D * rope.rotary_factor)
    half = rot // 2
    freqs = rope_freqs(rope, half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]  # [T,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if rope.attention_factor != 1.0:
        cos, sin = cos * rope.attention_factor, sin * rope.attention_factor
    x1, x2 = x[..., :half], x[..., half:rot]
    parts = [x1 * cos - x2 * sin, x1 * sin + x2 * cos]
    if rot < D:
        parts.append(x[..., rot:].astype(cos.dtype))
    return jnp.concatenate(parts, -1).astype(x.dtype)
