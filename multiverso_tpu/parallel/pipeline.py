"""Pipeline parallelism — GPipe over a mesh axis.

Not in the reference (a 2016 parameter server predates pipeline-parallel
training); included because PP completes this framework's parallelism
matrix (dp / tp / sp / ep / pp).

TPU-first design: the classic GPipe schedule expressed as pure SPMD —
``shard_map`` over the ``pp`` axis, stage weights stacked [pp, ...] and
sharded on the leading dim, and ONE ``lax.scan`` over
``num_micro + pp - 1`` ticks.  Every tick each stage applies its layers
to the activation it holds, then the activations rotate one stage
forward via ``ppermute`` (ICI neighbor exchange).  Stage 0 injects a
fresh microbatch per tick; the last stage banks its finished
microbatches.  Idle ticks (the pipeline bubble, (pp-1)/(M+pp-1) of the
work) compute on garbage and are masked out — the standard SPMD trade:
uniform code, no data-dependent control flow, XLA overlaps the permute
with compute.  Everything is differentiable: ``ppermute`` transposes to
the reverse rotation, so ``jax.grad`` yields exactly the backward
pipeline schedule.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["gpipe", "stage_pspec"]


def stage_pspec(ndim: int, axis_name: str = "pp"):
    """PartitionSpec for stacked stage params: [pp, ...] over ``axis_name``."""
    return P(axis_name, *([None] * (ndim - 1)))


def gpipe(stage_fn: Callable[[Any, jax.Array], jax.Array],
          stage_params: Any, x: jax.Array, mesh: Mesh,
          axis_name: str = "pp",
          batch_axis: str | None = "dp",
          param_specs: Any = None,
          remat_stages: bool = False) -> jax.Array:
    """Run ``x`` through ``pp`` pipeline stages, microbatched.

    - ``stage_fn(params_slice, h) -> h``: one stage's compute (e.g. a
      scan over its layer block); must preserve ``h``'s shape/dtype.
    - ``stage_params``: pytree whose leaves lead with the stage dim
      [pp, ...] (sharded over ``axis_name`` — use :func:`stage_pspec`).
    - ``x``: [M, Bm, ...] microbatched input.  Returns [M, Bm, ...]
      outputs — microbatch m's activations after ALL pp stages.
    - ``batch_axis``: mesh axis the microbatch dim Bm is sharded over
      (data parallel inside each stage), or None.
    - ``param_specs``: optional pytree of per-leaf ``PartitionSpec``s for
      the *trailing* weight dims (e.g. tensor-parallel layouts like
      ``P(None, "tp")`` per layer); ``gpipe`` prepends the stage axis
      and pads unnamed middle dims.  With tp-sharded weights the stage
      body is manual SPMD over that axis too — ``stage_fn`` must psum
      its row-parallel matmul outputs (see
      ``models/transformer.py`` pp×tp).  Default: weights replicated on
      every non-stage axis; pp then composes with dp only.
    - ``remat_stages``: wrap each stage tick in ``jax.checkpoint``.
      Under ``jax.grad`` this gives the 1F1B *memory* profile without
      1F1B's manual fwd/bwd interleaving: plain GPipe-as-scan saves
      every stage's internal activations for all M microbatches
      (O(M·layers_per_stage) per device); with remat only each tick's
      stage INPUT survives to the backward sweep — and that is the
      rotation buffer the scan carries anyway — so live memory drops to
      the microbatched input [M, Bm, d] plus one in-flight activation,
      the same O(pp)-in-flight bound 1F1B schedules target.  The cost is
      one extra forward per stage in the backward sweep, which is the
      standard remat trade everywhere else in this framework.  (1F1B's
      remaining advantage, bubble shape under interleaved virtual
      stages, needs per-tick fwd/bwd mixing that fights ``jax.grad``'s
      reverse-of-forward schedule — documented as out of scope.)  Must
      run under ``jax.jit`` (``jax.checkpoint`` inside ``shard_map`` has
      no eager path).
    """
    pp = int(mesh.shape[axis_name])
    M = int(x.shape[0])
    b_ax = batch_axis if (batch_axis and batch_axis in mesh.shape) else None
    x_spec = P(None, b_ax, *([None] * (x.ndim - 2)))
    if param_specs is None:
        p_spec = jax.tree_util.tree_map(
            lambda l: stage_pspec(l.ndim, axis_name), stage_params)
    else:
        p_spec = jax.tree_util.tree_map(
            lambda l, spec: P(axis_name,
                              *([None] * (l.ndim - 1 - len(spec))),
                              *spec),
            stage_params, param_specs,
            is_leaf=lambda t: isinstance(t, P))
    ring = [(s, (s + 1) % pp) for s in range(pp)]
    tick_fn = jax.checkpoint(stage_fn) if remat_stages else stage_fn

    def local(params_s, x_all):
        # params_s leaves: [1, ...] (this stage's slice); drop the dim.
        params_s = jax.tree_util.tree_map(lambda l: l[0], params_s)
        idx = jax.lax.axis_index(axis_name)
        buf = jnp.zeros_like(x_all[0])          # activation held right now
        outs = jnp.zeros_like(x_all)            # last stage's bank

        def tick(carry, t):
            buf, outs = carry
            # Stage 0 starts microbatch t (while t < M); other stages
            # work on what the previous tick's rotation handed them.
            inject = x_all[jnp.minimum(t, M - 1)]
            h = jnp.where(idx == 0, inject, buf)
            h = tick_fn(params_s, h)
            m = t - idx                         # microbatch this stage did
            bank = (idx == pp - 1) & (m >= 0) & (m < M)
            # Mask the ROW, not the whole bank — a full-buffer where()
            # would copy [M, Bm, d] every tick and defeat aliasing.
            pos = jnp.clip(m, 0, M - 1)
            outs = outs.at[pos].set(jnp.where(bank, h, outs[pos]))
            buf = jax.lax.ppermute(h, axis_name, ring)
            return (buf, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(M + pp - 1))
        # Only the last stage holds real outputs; replicate over pp so
        # the caller sees one logical array (psum of one-hot banks).
        outs = jax.lax.psum(
            jnp.where(idx == pp - 1, outs, jnp.zeros_like(outs)),
            axis_name)
        return outs

    return jax.shard_map(local, mesh=mesh, in_specs=(p_spec, x_spec),
                         out_specs=x_spec, check_vma=False)(stage_params, x)
